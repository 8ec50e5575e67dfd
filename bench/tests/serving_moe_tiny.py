"""A reduced MLA + held-share expert model and traffic for CPU runs of
the ``serving_moe`` driver."""

import time

from bench.harness import core

# the published configuration with every width cut to a CPU size: 8
# routed experts, this chip holding experts 4-7
TINY_MODEL = {"hidden_size": 64, "num_hidden_layers": 3,
              "num_attention_heads": 4, "num_key_value_heads": 4,
              "intermediate_size": 128, "vocab_size": 256,
              "kv_lora_rank": 16, "qk_rope_head_dim": 8,
              "qk_nope_head_dim": 8, "v_head_dim": 16,
              "moe_intermediate_size": 32, "num_experts_per_tok": 2,
              "n_routed_experts": 4, "expert_offset": 4,
              "published": {"n_routed_experts": 8}}
TINY_TRAFFIC = {"prompt_pad": 16,
                "prompt_len": {"median": 8, "sigma": 0.5, "min": 2, "max": 16}}
E2E = [{"name": "serve_tok_s", "unit": "tokens/s"},
       {"name": "itl_ms", "unit": "ms"}, {"name": "setup_s", "unit": "s"}]
PER_LAYER = [{"name": n, "unit": "%"} for n in (
    "moe_experts.roofline", "moe_decode_step.roofline", "moe_serve.mfu")]
# Set from this tiny model's readings on the CPU, 4 requests x 6 tokens:
# sound runs on three seeds read a widest gap of 0-0.0113 and a mean gap
# of 0-0.00047; on seed 5 the fp8 control reads 0.181 and 0.0131, the
# int8 control 0.462 and 0.0206
LIMITS = {"served_token_gap": 0.1, "mean_gap": 0.005}


def tiny(**over):
    cfg = core.load_json("configs", "moonlight-16b-a3b")
    cfg.update(TINY_MODEL)
    tr = core.load_json("traffic", "moe_decode")
    tr.update(TINY_TRAFFIC, **over)
    tr["horizon"] = tr["prompt_pad"] + tr["max_new"]
    return cfg, tr


def run(cfg, tr, seed=2 ** 32 + 3, driver_cls=None):
    """One ``generate`` call (``seconds=0``) through the whole run."""
    return core.run_cell({"name": "serve.tiny_moe", "chips": 1}, cfg, tr,
                         E2E, PER_LAYER, LIMITS, seed, 0.0, False,
                         time.perf_counter(), require_chip=False,
                         log=print, driver_cls=driver_cls)
