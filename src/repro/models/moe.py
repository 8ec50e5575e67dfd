"""Mixture-of-Experts layers.

``moe_apply`` (family "moe") is a capacity-factor layer that DROPS tokens
past an expert's capacity (Qwen3-MoE style: top-k softmax-renormalized
gates, no shared expert). ``held_moe_apply`` (family "mla_moe") is
dropless and holds one chip's share of the experts (DeepSeek-V3 style:
sigmoid scores with a selection bias, normalised top-k weights, shared
experts); see below.

Capacity layer:

TPU-native dispatch: tokens are processed in groups of ``GROUP`` tokens; each
group dispatches into per-expert capacity buffers with a deterministic
einsum (Mesh-TensorFlow formulation). Group size is deliberately small —
dispatch/combine FLOPs are 2*tokens*cf*GROUP*k*d, *independent of E*, so
small groups keep dispatch overhead ~10% of expert compute (see
EXPERIMENTS.md §Perf napkin math). Experts are sharded over the "model" mesh
axis (EP); XLA SPMD inserts the all-to-alls.
"""

from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs import ModelConfig
from repro.dist.collectives import current_act_transport
from repro.dist.sharding import constrain
from repro.kernels.expert_a2a import expert_a2a
from repro.kernels.expert_gmm import expert_gmm
from repro.models.common import Spec, swiglu

GROUP = 512  # tokens per dispatch group (upper bound)


def moe_specs(cfg: ModelConfig) -> Dict[str, Spec]:
    d, e, f = cfg.d_model, cfg.n_experts, cfg.d_ff_expert
    return {
        "router": Spec((d, e), ("embed", "experts"), dtype=jnp.float32),
        "w_gate": Spec((e, d, f), ("experts", "embed", "expert_mlp")),
        "w_up": Spec((e, d, f), ("experts", "embed", "expert_mlp")),
        "w_down": Spec((e, f, d), ("experts", "expert_mlp", "embed")),
    }


def _group_size(n_tokens: int) -> int:
    g = min(GROUP, n_tokens)
    while n_tokens % g:
        g -= 1
    return g


def capacity(cfg: ModelConfig, group: int) -> int:
    return max(1, math.ceil(cfg.capacity_factor * group * cfg.top_k / cfg.n_experts))


def moe_apply(cfg: ModelConfig, p, x: jnp.ndarray, mode: str = "train"
              ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """x: (B, S, d) -> (B, S, d), aux metrics (load-balance loss etc.).

    Under expert-parallel decode with ``act_transport="int8"``, the token
    dispatch (the expert all-to-all's payload) routes through the
    ``expert_a2a`` tunable op — int8 blockwise on the wire, dequantized on
    the expert shard. Train/prefill keep the bf16 einsum dispatch so the
    training loss path stays bit-identical.
    """
    b, s, d = x.shape
    n_tokens = b * s
    m = _group_size(n_tokens)
    g = n_tokens // m
    e, k = cfg.n_experts, cfg.top_k
    c = capacity(cfg, m)

    xt = constrain(x.reshape(g, m, d), "batch", None, "act_embed")
    logits = constrain(
        jnp.einsum("gmd,de->gme", xt.astype(jnp.float32), p["router"]),
        "batch", None, None)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, sel = jax.lax.top_k(probs, k)                     # (g,m,k)
    gate_vals = gate_vals / jnp.sum(gate_vals, -1, keepdims=True)  # renorm (Qwen3)

    onehot = jax.nn.one_hot(sel, e, dtype=jnp.float32)           # (g,m,k,e)
    flat = onehot.reshape(g, m * k, e)
    # position of each (token, choice) within its expert's buffer
    pos_in_e = jnp.cumsum(flat, axis=1) - flat                   # (g,mk,e)
    slot = jnp.sum(pos_in_e * flat, axis=-1).astype(jnp.int32)   # (g,mk)
    keep = (slot < c).astype(jnp.float32).reshape(g, m, k)
    slot_oh = jax.nn.one_hot(slot.reshape(g, m, k), c, dtype=jnp.float32)

    # dispatch mask (g,m,e,c) and gate-weighted combine mask
    dispatch = constrain(
        jnp.einsum("gmke,gmkc->gmec", onehot * keep[..., None], slot_oh),
        "batch", None, "experts", None)
    combine = constrain(
        jnp.einsum("gmke,gmkc->gmec",
                   onehot * (gate_vals * keep)[..., None], slot_oh),
        "batch", None, "experts", None)

    xe = jnp.einsum("gmec,gmd->gecd", dispatch.astype(x.dtype), xt)  # (g,e,c,d)
    if mode == "decode" and current_act_transport() == "int8":
        xe = expert_a2a(xe)
    else:
        xe = constrain(xe, "batch", "experts", None, "act_embed")
    h_gate = constrain(jnp.einsum("gecd,edf->gecf", xe, p["w_gate"]),
                       "batch", "experts", None, None)
    h_up = jnp.einsum("gecd,edf->gecf", xe, p["w_up"])
    ye = constrain(jnp.einsum("gecf,efd->gecd",
                              jax.nn.silu(h_gate) * h_up, p["w_down"]),
                   "batch", "experts", None, "act_embed")
    y = constrain(jnp.einsum("gmec,gecd->gmd", combine.astype(x.dtype), ye),
                  "batch", None, "act_embed")

    # aux: load-balance loss (Switch style) + router z-loss + drop fraction
    density = jnp.mean(onehot, axis=(1, 2))                      # (g,e) selection freq
    density_prob = jnp.mean(probs, axis=1)                       # (g,e)
    lb_loss = e * jnp.mean(jnp.sum(density * density_prob, axis=-1))
    z_loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    dropped = 1.0 - jnp.mean(keep)
    aux = {"moe_lb_loss": lb_loss, "moe_z_loss": z_loss,
           "moe_drop_frac": dropped}
    return y.reshape(b, s, d), aux


# ---------------------------------------------------------------------------
# dropless layer holding one chip's share of the experts (family "mla_moe")
# ---------------------------------------------------------------------------
#
# The router keeps the published width and routes every token over all
# ``n_experts`` in float32. Of the chosen (token, choice) pairs, those whose
# expert this chip holds (``expert_offset`` .. + ``experts_held`` - 1) go
# through the ``expert_gmm`` grouped matmul, in proportion to their number:
# no capacity, no dropped pair. Pairs routed to absent experts add nothing
# here (in expert parallelism another chip computes them; on one chip the
# layer runs without that exchange). The shared experts run on every token.

HELD_COUNTERS = ("moe_held_pairs", "moe_max_expert_tokens")


def add_counters(acc, new):
    """``acc`` plus ``new``, key by key: counts add up (over layers,
    over steps); ``moe_max_expert_tokens`` keeps the larger."""
    return {k: jnp.maximum(acc[k], v)
            if k == "moe_max_expert_tokens" and k in acc
            else acc.get(k, 0) + v for k, v in new.items()}


def held_moe_specs(cfg: ModelConfig) -> Dict[str, Spec]:
    """One layer's router (every expert: it is on every chip), selection
    bias, held experts and shared experts."""
    d, e, eh, f = cfg.d_model, cfg.n_experts, cfg.n_held_experts, \
        cfg.d_ff_expert
    s = {
        "router": Spec((d, e), ("embed", None)),
        "router_bias": Spec((e,), (None,), init="zeros"),
        "w_gate": Spec((eh, d, f), ("experts", "embed", "expert_mlp")),
        "w_up": Spec((eh, d, f), ("experts", "embed", "expert_mlp")),
        "w_down": Spec((eh, f, d), ("experts", "expert_mlp", "embed")),
    }
    if cfg.n_shared_experts:
        fs = cfg.n_shared_experts * f
        s["shared"] = {"gate": Spec((d, fs), ("embed", "mlp")),
                       "up": Spec((d, fs), ("embed", "mlp")),
                       "down": Spec((fs, d), ("mlp", "embed"))}
    return s


def route(cfg: ModelConfig, router, bias, x2):
    """Top-k experts of each token over all ``n_experts`` and their
    weights, float32 (DeepSeek-V3's ``noaux_tc`` with one group): chosen
    on sigmoid score + ``bias``, weighed by the unbiased scores over their
    sum, times ``routed_scaling``. x2: (T, d) -> (sel (T, k) int32,
    w (T, k))."""
    logits = jnp.dot(x2.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, sel = jax.lax.top_k(scores + bias.astype(jnp.float32), cfg.top_k)
    w = jnp.take_along_axis(scores, sel, axis=-1)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return sel.astype(jnp.int32), w * cfg.routed_scaling


def held_moe_apply(cfg: ModelConfig, p, experts, x: jnp.ndarray, layer,
                   mode: str = "train"):
    """x: (B, S, d) -> (B, S, d), counters. ``p`` holds this layer's
    router, bias and shared experts; ``experts`` the held experts' stacked
    weights of every MoE layer (``w_gate``, ``w_up``: (L, E, d, f);
    ``w_down``: (L, E, f, d)), of which ``layer`` is this one's index, so
    that no layer's expert weights are sliced into a copy. Training runs
    the grouped matmul's differentiable reference; prefill and decode the
    kernel. Counters: pairs routed onto held experts, and the most tokens
    one held expert took."""
    b, s, d = x.shape
    x2 = x.reshape(b * s, d)
    sel, w = route(cfg, p["router"], p["router_bias"], x2)
    # each pair's held expert (0 .. experts_held - 1), or -1 where another
    # chip holds it
    local = sel - cfg.expert_offset
    groups = jnp.where((local >= 0) & (local < cfg.n_held_experts), local, -1)
    y_pairs = expert_gmm(x2, groups, experts["w_gate"], experts["w_up"],
                         experts["w_down"], layer, use_ref=mode == "train")
    y = jnp.einsum("tk,tkd->td", w, y_pairs.astype(jnp.float32)
                   ).astype(x.dtype)
    if cfg.n_shared_experts:
        sh = p["shared"]
        y = y + swiglu(x2, sh["gate"], sh["up"], sh["down"])
    counts = jnp.sum(groups.reshape(-1)[:, None]
                     == jnp.arange(cfg.n_held_experts), 0)
    aux = {"moe_held_pairs": jnp.sum(counts).astype(jnp.int32),
           "moe_max_expert_tokens": jnp.max(counts).astype(jnp.int32)}
    return y.reshape(b, s, d), aux
