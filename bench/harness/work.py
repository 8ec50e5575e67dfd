"""The least work each measured operation needs, from its shapes alone.

A roofline share is the least time the chip could take, the larger of
least operations over peak FLOP/s and least bytes over peak HBM bytes/s,
over the time the device took. Everything here is counted from the
published configuration and the traffic's sizes, never from how the
program happens to compute it, so a PR that replaces a kernel or an
algorithm is measured against the same floor.

Model configurations are the dicts of ``bench/configs/*.json`` (Hugging
Face key names). A multiply-add counts as two operations; weights are
bf16 (2 bytes).
"""

from __future__ import annotations

from typing import Iterable, Tuple

BF16 = 2


def roofline_s(flops: float, nbytes: float, peak: dict) -> float:
    """Least seconds for ``flops`` and ``nbytes`` on a device with the
    peaks row ``peak`` (see ``peaks.py``)."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])


# ---------------------------------------------------------------------------
# compaction
# ---------------------------------------------------------------------------

def gather_bytes(input_bytes: int, output_bytes: int) -> int:
    """Least HBM bytes of a rewrite that moves data and computes nothing:
    every input byte read once, every output byte written once."""
    return int(input_bytes) + int(output_bytes)


def filter_bytes(kept_bytes: int) -> int:
    """Least HBM bytes of a filtering rewrite: only the kept rows need to
    be read, and each is written once. The keep mask is made on the
    host, so it costs the device nothing."""
    return 2 * int(kept_bytes)


# ---------------------------------------------------------------------------
# MLA decoder (MiniCPM3 / DeepSeek-V2 attention, SwiGLU MLP)
# ---------------------------------------------------------------------------

def _dims(cfg: dict):
    return (cfg["num_hidden_layers"], cfg["hidden_size"],
            cfg["num_attention_heads"], cfg["intermediate_size"],
            cfg["vocab_size"], cfg["q_lora_rank"], cfg["kv_lora_rank"],
            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"])


def layer_matmul_params(cfg: dict) -> int:
    """Weights one token multiplies through in one layer."""
    _, d, h, ff, _, rq, rkv, dn, dr, dv = _dims(cfg)
    return (d * rq + rq * h * (dn + dr) + d * (rkv + dr) + rkv * h * dn
            + rkv * h * dv + h * dv * d + 3 * d * ff)


def layer_norm_params(cfg: dict) -> int:
    _, d, _, _, _, rq, rkv, *_ = _dims(cfg)
    return 2 * d + rq + rkv


def weight_bytes_read(cfg: dict) -> int:
    """Bytes of every weight a forward step reads once: all but the
    embedding table, of which only the rows of the step's tokens are
    read (counted by the callers)."""
    n_l, d, *_ = _dims(cfg)
    v = cfg["vocab_size"]
    return BF16 * (n_l * (layer_matmul_params(cfg) + layer_norm_params(cfg))
                   + d * v + d)


def _latent_bytes_per_position(cfg: dict) -> int:
    n_l, *_ = _dims(cfg)
    return BF16 * n_l * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])


def decode_step(cfg: dict, live: Iterable[int]) -> Tuple[float, float]:
    """Least (operations, bytes) of one decode step in which the active
    slots attend over ``live`` positions each (the new token included).

    Operations: every weight once per active slot (with the key and value
    up-projections absorbed into the query and the output, MLA's decode
    form), plus attention in latent form: scores against the latent and
    the shared rope key, and the weighted sum of latents. Bytes: the
    weights once, the active tokens' embedding rows, and the live latent
    cache once."""
    live = [int(n) for n in live]
    n_l, d, h, _, v, _, rkv, _, dr, _ = _dims(cfg)
    n_act = len(live)
    pos = sum(live)
    flops = (2.0 * n_act * (n_l * layer_matmul_params(cfg) + d * v)
             + 2.0 * n_l * h * (2 * rkv + dr) * pos)
    nbytes = (weight_bytes_read(cfg) + BF16 * d * n_act
              + _latent_bytes_per_position(cfg) * pos)
    return flops, float(nbytes)


def prefill(cfg: dict, n: int) -> Tuple[float, float]:
    """Least (operations, bytes) of prefilling one request of ``n`` real
    prompt tokens (padding does not count): every layer's weights for
    each token, causal attention over the expanded per-head keys and
    values (the cheaper form at prefill), the output head for the last
    position only. Bytes: the weights once, the tokens' embedding rows,
    and the latent cache written once."""
    n = int(n)
    n_l, d, h, _, v, _, _, dn, dr, dv = _dims(cfg)
    pairs = n * (n + 1) / 2
    flops = (2.0 * n * n_l * layer_matmul_params(cfg) + 2.0 * d * v
             + 2.0 * n_l * h * (dn + dr + dv) * pairs)
    nbytes = (weight_bytes_read(cfg) + BF16 * d * n
              + _latent_bytes_per_position(cfg) * n)
    return flops, float(nbytes)


def slot_schedule(max_new: int, n_slots: int, n_requests: int):
    """Which requests decode in each step of a slot-table engine that
    admits pending requests in order into free slots before each step,
    takes each request's first token from its prefill and decodes the
    other ``max_new - 1``. Returns a list over steps of lists of
    (request, tokens emitted so far) for the active slots."""
    active, emitted, nxt, steps = [], {}, 0, []
    while True:
        while len(active) < n_slots and nxt < n_requests:
            emitted[nxt] = 1
            if emitted[nxt] < max_new:
                active.append(nxt)
            nxt += 1
        if not active:
            return steps
        steps.append([(r, emitted[r]) for r in active])
        for r in active:
            emitted[r] += 1
        active = [r for r in active if emitted[r] < max_new]


def generate_call(cfg: dict, lens: Iterable[int], max_new: int,
                  n_slots: int) -> dict:
    """Least work of one ``generate`` call: per-request prefill and every
    decode step of the slot schedule. Returns sums of operations and
    bytes for each, the per-step bounds, and the number of decode
    steps."""
    lens = [int(n) for n in lens]
    pre = [prefill(cfg, n) for n in lens]
    dec = [decode_step(cfg, [lens[r] + e for r, e in step])
           for step in slot_schedule(max_new, n_slots, len(lens))]
    return {"prefill": pre, "decode": dec,
            "decode_steps": len(dec),
            "model_flops": sum(f for f, _ in pre) + sum(f for f, _ in dec)}
