"""A reduced MLA model and traffic for CPU runs of the serving driver."""

import math
import time

from bench.harness import core

# the published configuration with every width cut to a CPU size
TINY_MODEL = {"hidden_size": 64, "num_hidden_layers": 2,
              "num_attention_heads": 4, "num_key_value_heads": 2,
              "intermediate_size": 128, "vocab_size": 256,
              "q_lora_rank": 32, "kv_lora_rank": 16, "qk_rope_head_dim": 8,
              "qk_nope_head_dim": 8, "v_head_dim": 16,
              "scale_depth": math.sqrt(2), "dim_model_base": 64}
TINY_TRAFFIC = {"prompt_pad": 16,
                "prompt_len": {"median": 8, "sigma": 0.5, "min": 2, "max": 16}}
E2E = [{"name": "serve_tok_s", "unit": "tokens/s"},
       {"name": "itl_ms", "unit": "ms"}, {"name": "setup_s", "unit": "s"}]
PER_LAYER = [{"name": n, "unit": "%"} for n in (
    "idle_share.serve", "decode_step.roofline", "prefill_step.roofline",
    "serve.mfu")]
# Set from this tiny model's readings on the CPU, 8 requests x 16 tokens,
# four seeds: sound runs read a widest gap of 0.0022-0.0213 and a mean
# gap of 0.00003-0.00030, the fp8 control 0.161-0.192 and 0.0059-0.0107
# (the int8 control reads like the sound runs at this size)
LIMITS = {"served_token_gap": 0.08, "mean_gap": 0.0015}


def tiny(traffic_name="decode", **over):
    cfg = core.load_json("configs", "minicpm3-4b")
    cfg.update(TINY_MODEL)
    tr = core.load_json("traffic", traffic_name)
    tr.update(TINY_TRAFFIC, **over)
    tr["horizon"] = tr["prompt_pad"] + tr["max_new"]
    return cfg, tr


def run(cfg, tr, seed=2 ** 32 + 3, driver_cls=None):
    """One ``generate`` call (``seconds=0``) through the whole run."""
    return core.run_cell({"name": "serve.tiny", "chips": 1}, cfg, tr, E2E,
                         PER_LAYER, LIMITS, seed, 0.0, False,
                         time.perf_counter(), require_chip=False,
                         log=lambda s: None, driver_cls=driver_cls)
