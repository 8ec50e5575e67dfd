"""Driver: the model server's slot engine (``launch/serve.py::generate``,
``stream="slots"``) on an MLA model with held-share sparse experts (the
DeepSeek-V3 block, ``family="mla_moe"``), at its published widths.

Everything is the ``serving`` driver's (``serving.py``): the requests,
the closed loop of ``generate`` calls, the end-to-end numbers and the
check's two gaps over a sample of served tokens. What differs is the
model: its configuration keys and the program's fields they set, its
weights (``bench/harness/weights_moe.py``) and its float32 reference
(``bench/reference/mla_moe.py``). Each call also records the decode
steps' expert counters from ``_generate_slots.last_stats``:
``moe_held_pairs`` (pairs routed onto held experts, every row the steps
computed) and ``moe_max_expert_tokens`` (the most tokens one held expert
took in one step of one layer).
"""

from __future__ import annotations

import time

import numpy as np

from bench.drivers import serving
from bench.harness import weights as wlib
from bench.harness import weights_moe as wmoe
from bench.reference import mla_moe as moe_ref

COUNTERS = ("moe_held_pairs", "moe_max_expert_tokens")

# configuration keys and the program's ModelConfig fields they set
FIELDS = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
          "num_attention_heads": "n_heads",
          "num_key_value_heads": "n_kv_heads",
          "intermediate_size": "d_ff", "vocab_size": "vocab",
          "kv_lora_rank": "kv_lora_rank",
          "qk_rope_head_dim": "rope_head_dim",
          "qk_nope_head_dim": "nope_head_dim", "v_head_dim": "v_head_dim",
          "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
          "tie_word_embeddings": "tie_embeddings",
          "num_experts_per_tok": "top_k",
          "moe_intermediate_size": "d_ff_expert",
          "n_shared_experts": "n_shared_experts",
          "first_k_dense_replace": "first_k_dense",
          "routed_scaling_factor": "routed_scaling",
          "n_routed_experts": "experts_held",
          "expert_offset": "expert_offset"}
# what the program computes; anything else it cannot run
REQUIRED = {"scoring_func": "sigmoid", "topk_method": "noaux_tc",
            "n_group": 1, "topk_group": 1, "q_lora_rank": None,
            "moe_layer_freq": 1, "num_nextn_predict_layers": 0,
            "norm_topk_prob": True,
            "hidden_act": "silu", "attention_bias": False}


def program_config(config: dict):
    """The program's ModelConfig for ``config``: every published routed
    expert in the router, ``n_routed_experts`` of them held here."""
    import dataclasses

    from repro.configs import get_config

    for key, want in REQUIRED.items():
        if config.get(key) != want:
            raise ValueError(f"the program cannot run {key}={config.get(key)!r}"
                             f" (it computes {key}={want!r})")
    if config.get("rope_scaling") is not None:
        raise ValueError("the program has no RoPE scaling")
    base = get_config(config["program_arch"])
    return dataclasses.replace(
        base, name=config["name"], q_lora_rank=0,
        n_experts=config["published"]["n_routed_experts"],
        **{field: config[key] for key, field in FIELDS.items()})


class Driver(serving.Driver):
    # -- set-up ------------------------------------------------------------
    def setup(self):
        import jax

        from repro.dist import sharding as shd
        from repro.launch import serve
        from repro.launch.mesh import make_local_mesh
        from repro.models import transformer

        t = self.traffic
        self.serve = serve
        self.cfg = program_config(self.config)
        abstract = transformer.abstract_params(self.cfg)
        got, want = wlib.shapes_of(abstract), moe_ref.model_shapes(
            self.config)
        if got != want:
            raise ValueError(f"the program's weights {got} are not the "
                             f"model the reference computes {want}")
        mesh = make_local_mesh(model_parallel=1)
        rules = shd.PRESETS["serve_sp"]
        t0 = time.perf_counter()
        self.params = jax.block_until_ready(wmoe.build(
            self.seed, abstract, self.config["router_bias_std"],
            shd.tree_shardings(abstract, transformer.param_axes(self.cfg),
                               mesh, rules)))
        self.kwargs = dict(temperature=0.0, mesh=mesh, rules=rules,
                           stream="slots", slots=t["slots"],
                           horizon=t["horizon"], kv_storage="bf16")
        t1 = time.perf_counter()
        prompts, lens = self.requests(-1)
        serve.generate(self.cfg, self.params, prompts, prompt_lens=lens,
                       max_new=min(t["warm_new"], t["max_new"]),
                       **self.kwargs)
        self.log(f"[serving_moe] weights made in {t1 - t0:.3f} s; warm-up "
                 f"call {time.perf_counter() - t1:.3f} s")

    # -- one iteration -----------------------------------------------------
    def step(self):
        super().step()
        stats = self.serve._generate_slots.last_stats
        call = self.calls[-1]
        call.update({k: int(stats[k]) for k in COUNTERS})
        c = self.config
        pairs = call["decode_steps"] * self.traffic["slots"] * \
            c["num_experts_per_tok"] * (c["num_hidden_layers"]
                                        - c["first_k_dense_replace"])
        self.log(f"[serving_moe] call {len(self.calls) - 1}: "
                 f"{call['moe_held_pairs']} of {pairs} decode pairs on held "
                 f"experts, at most {call['moe_max_expert_tokens']} tokens "
                 f"to one expert in a step")

    def records(self) -> list:
        return [dict(r, **{k: c[k] for k in COUNTERS})
                for r, c in zip(super().records(), self.calls)]

    # -- the check -----------------------------------------------------------
    def gaps(self, quant=None) -> dict:
        """``serving.Driver.gaps`` against this model's reference."""
        import jax.numpy as jnp

        seq, picks, served = self.sequences()
        ref = moe_ref.logits(self.config, self.seed, seq, picks)
        best = ref.max(-1)
        if quant is None:
            chosen = jnp.asarray(served)
        else:
            chosen = moe_ref.logits(self.config, self.seed, seq, picks,
                                    quant=quant).argmax(-1)
        gap = best - jnp.take_along_axis(ref, chosen[..., None], -1)[..., 0]
        gap = np.asarray(gap)
        return {"served_token_gap": float(gap.max()),
                "mean_gap": float(gap.mean()),
                "agree": int((gap == 0).sum()), "tokens": int(gap.size),
                "row_std": float(np.asarray(ref.std(-1)).mean())}
