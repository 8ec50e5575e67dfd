"""The least work of the MLA + held-share expert decoder (DeepSeek-V3
block, ``bench/configs/moonlight-16b-a3b.json``), counted from the
published configuration, the traffic and the program's expert counters,
never from how the program computes.

The chip holds ``n_routed_experts`` of the ``published`` routed experts
of each expert layer; everything else (attention, the leading dense
layers, router, shared experts, embedding, output head) is whole. A
multiply-add counts as two operations; weights are bf16. The held
experts' operations come from ``moe_held_pairs`` (pairs routed onto a
held expert, summed over layers and decode steps) where the counters are
read, and from the configuration's expected share (``top_k`` x held /
published experts a token) where they are not (prefill, and MFU).
"""

from __future__ import annotations

from typing import Iterable, Tuple

from bench.harness.work import BF16, slot_schedule


def _dims(cfg: dict):
    return (cfg["num_hidden_layers"], cfg["hidden_size"],
            cfg["num_attention_heads"], cfg["vocab_size"],
            cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"])


def moe_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def expert_params(cfg: dict) -> int:
    """One routed expert's SwiGLU weights."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def attention_params(cfg: dict) -> int:
    _, d, h, _, rkv, dn, dr, dv = _dims(cfg)
    return (d * h * (dn + dr) + d * (rkv + dr) + rkv * h * dn
            + rkv * h * dv + h * dv * d)


def token_params(cfg: dict) -> int:
    """Weights every token multiplies through, the routed experts and the
    output head aside: attention, the dense layers' MLP, and each expert
    layer's router and shared experts."""
    n_l, d, *_ = _dims(cfg)
    k = cfg["first_k_dense_replace"]
    return (n_l * attention_params(cfg)
            + k * 3 * d * cfg["intermediate_size"]
            + moe_layers(cfg) * (d * cfg["published"]["n_routed_experts"]
                                 + cfg["n_shared_experts"]
                                 * expert_params(cfg)))


def held_share(cfg: dict) -> float:
    """Routed experts a token goes through on this chip, expected: its
    ``top_k`` choices times the share of experts held here, per layer."""
    return cfg["num_experts_per_tok"] * cfg["n_routed_experts"] / \
        cfg["published"]["n_routed_experts"]


def held_expert_bytes(cfg: dict) -> int:
    """Every held expert's weights of every expert layer, once."""
    return BF16 * moe_layers(cfg) * cfg["n_routed_experts"] * \
        expert_params(cfg)


def weight_bytes_read(cfg: dict) -> int:
    """Bytes of every weight on the chip a forward step reads once: all
    but the embedding table (only the step's rows, counted by callers)."""
    n_l, d, _, v, rkv, *_ = _dims(cfg)
    norms = n_l * (2 * d + rkv) + d
    bias = moe_layers(cfg) * cfg["published"]["n_routed_experts"]
    return BF16 * (token_params(cfg) + d * v + norms + bias) + \
        held_expert_bytes(cfg)


def _latent_bytes_per_position(cfg: dict) -> int:
    n_l, *_ = _dims(cfg)
    return BF16 * n_l * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])


def experts_decode(cfg: dict, held_pairs: int, steps: int
                   ) -> Tuple[float, float]:
    """Least (operations, bytes) of the held experts' work in ``steps``
    decode steps that routed ``held_pairs`` pairs onto held experts:
    each held expert's weights once a step, each pair's token read and
    its output written once."""
    d = cfg["hidden_size"]
    flops = 2.0 * expert_params(cfg) * held_pairs
    nbytes = steps * held_expert_bytes(cfg) + 2 * BF16 * d * held_pairs
    return flops, float(nbytes)


def decode_step(cfg: dict, live: Iterable[int], held_pairs: float
                ) -> Tuple[float, float]:
    """Least (operations, bytes) of one decode step in which the active
    slots attend over ``live`` positions each and ``held_pairs`` pairs go
    to held experts: every weight once per active slot (latent attention
    in absorbed form), the held pairs through their experts; bytes the
    weights once, the active tokens' embedding rows, the live latent
    cache once."""
    live = [int(n) for n in live]
    n_l, d, h, v, rkv, _, dr, _ = _dims(cfg)
    n_act, pos = len(live), sum(live)
    flops = (2.0 * n_act * (token_params(cfg) + d * v)
             + 2.0 * expert_params(cfg) * held_pairs
             + 2.0 * n_l * h * (2 * rkv + dr) * pos)
    nbytes = (weight_bytes_read(cfg) + BF16 * d * n_act
              + _latent_bytes_per_position(cfg) * pos)
    return flops, float(nbytes)


def prefill_flops(cfg: dict, n: int) -> float:
    """Model operations of prefilling ``n`` real prompt tokens: every
    token through the layers (the held experts at their expected share),
    causal attention over expanded per-head keys and values, the output
    head at the last position."""
    n_l, d, h, v, _, dn, dr, dv = _dims(cfg)
    per_token = token_params(cfg) + held_share(cfg) * moe_layers(cfg) * \
        expert_params(cfg)
    return (2.0 * n * per_token + 2.0 * d * v
            + 2.0 * n_l * h * (dn + dr + dv) * n * (n + 1) / 2)


def generate_call(cfg: dict, lens: Iterable[int], max_new: int,
                  n_slots: int, held_pairs: int) -> dict:
    """Least work of one ``generate`` call: the decode steps of the slot
    schedule, the call's ``held_pairs`` spread evenly over them, and the
    model operations of every real prompt and generated token (the held
    experts at their expected share)."""
    lens = [int(n) for n in lens]
    sched = slot_schedule(max_new, n_slots, len(lens))
    per = held_pairs / max(1, len(sched))
    dec = [decode_step(cfg, [lens[r] + e for r, e in step], per)
           for step in sched]
    n_l, d, h, v, rkv, _, dr, _ = _dims(cfg)
    per_token = token_params(cfg) + d * v + held_share(cfg) * \
        moe_layers(cfg) * expert_params(cfg)
    dec_model = sum(2.0 * len(step) * per_token
                    + 2.0 * n_l * h * (2 * rkv + dr)
                    * sum(lens[r] + e for r, e in step) for step in sched)
    return {"decode": dec, "decode_steps": len(dec),
            "model_flops": sum(prefill_flops(cfg, n) for n in lens)
            + dec_model}
