"""Plain float32 reference of the DeepSeek-V3 block as Moonlight-16B-A3B
configures it: Multi-head Latent Attention without query compression,
one leading dense SwiGLU layer, then layers of routed and shared experts.
``jax.numpy`` at the highest matmul precision, one causal forward over
whole sequences, no cache, no batching tricks, no kernel; each layer's
weights are made from the seed (``bench/harness/weights_moe.py``) just
before it runs. It imports nothing of the program, and the MiniCPM3
reference's helpers (``mla.py``) by import.

Per layer, with ``rms(x, w) = x / sqrt(mean(x^2) + eps) * w``:

    h      = rms(x, ln1)
    q      = h @ wq                                 -> heads x (nope | rope)
    kv     = h @ wkv_a                              -> (latent | k_rope)
    latent = rms(kv[:kv_lora_rank], kv_norm)
    k      = (latent @ wk_b | rope(k_rope), shared by every head)
    v      = latent @ wv_b
    x     += (softmax(q k^T / sqrt(nope + rope), causal) v) @ wo
    h2     = rms(x, ln2)
    x     += mlp(h2)                                  (layers < first_k_dense_replace)
    x     += sum_e w_e mlp_e(h2) + shared_mlp(h2)    (the other layers)

with ``mlp(h) = (silu(h @ gate) * (h @ up)) @ down``. Routing, float32:
``s = sigmoid(h2 @ router)`` over every published routed expert; the
six chosen are the top six of ``s + bias``; their weights are their
``s`` over the sum of the six (plus 1e-20), times
``routed_scaling_factor``. Only the experts held here (``expert_offset``
and the ``n_routed_experts`` after it) are computed, each over every
token with its weight (zero where it was not chosen): what a pair routed
to another chip's expert would add is left out, as the program leaves it
out.

Departures from the published model: RoPE pairs rotate-half where the
published code de-interleaves the rope columns first (with random
weights, a fixed permutation of those columns); random weights, the
selection bias included.

``quant`` makes the control: every linear layer, the router and the
experts included, computed from weights and inputs rounded to
``"int8"`` or ``"fp8"`` (``mla.py``'s rounding).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import weights as wlib
from bench.harness import weights_moe as wmoe
from bench.reference.mla import QUERY_BLOCK, _linear, _rms, _rope


def attention_shapes(cfg: dict) -> dict:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    rkv = cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    return {"layers/attn/wq": (d, h, dn + dr),
            "layers/attn/wkv_a": (d, rkv + dr),
            "layers/attn/wk_b": (rkv, h, dn), "layers/attn/wv_b": (rkv, h, dv),
            "layers/attn/wo": (h, dv, d), "layers/attn/kv_norm": (rkv,),
            "layers/ln1": (d,), "layers/ln2": (d,)}


def dense_shapes(cfg: dict) -> dict:
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    return {"dense_mlp/gate": (d, ff), "dense_mlp/up": (d, ff),
            "dense_mlp/down": (ff, d)}


def moe_shapes(cfg: dict) -> dict:
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    e, eh = cfg["published"]["n_routed_experts"], cfg["n_routed_experts"]
    fs = cfg["n_shared_experts"] * f
    return {"moe/router": (d, e), "moe/router_bias": (e,),
            "moe/w_gate": (eh, d, f), "moe/w_up": (eh, d, f),
            "moe/w_down": (eh, f, d), "moe/shared/gate": (d, fs),
            "moe/shared/up": (d, fs), "moe/shared/down": (fs, d)}


def model_shapes(cfg: dict) -> dict:
    """``{path: shape}`` of the whole model, stacked leaves with their
    layer count first: what the program's weight tree must hold."""
    d, v, n_l = cfg["hidden_size"], cfg["vocab_size"], \
        cfg["num_hidden_layers"]
    k = cfg["first_k_dense_replace"]
    out = {p: (n_l,) + s for p, s in attention_shapes(cfg).items()}
    out.update({p: (k,) + s for p, s in dense_shapes(cfg).items()})
    out.update({p: (n_l - k,) + s for p, s in moe_shapes(cfg).items()})
    out.update({"embed": (v, d), "final_norm": (d,), "lm_head": (d, v)})
    return out


def _strip(w):
    return {k.split("/", 1)[1]: v.astype(jnp.float32) for k, v in w.items()}


def _attention(cfg, quant, x, w):
    eps = cfg["rms_norm_eps"]
    rkv, dn = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"]
    s = x.shape[1]
    h = _rms(x, w["ln1"], eps)
    q = _linear(h, w["attn/wq"], 1, quant)                   # (B,S,H,dn+dr)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:],
                                            cfg["rope_theta"])], -1)
    kv = _linear(h, w["attn/wkv_a"], 1, quant)
    latent = _rms(kv[..., :rkv], w["attn/kv_norm"], eps)
    k_rope = _rope(kv[..., rkv:], cfg["rope_theta"])        # (B,S,dr)
    k_nope = _linear(latent, w["attn/wk_b"], 1, quant)       # (B,S,H,dn)
    v = _linear(latent, w["attn/wv_b"], 1, quant)            # (B,S,H,dv)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_rope[:, :, None, :], k_nope.shape[:3] + k_rope.shape[-1:])], -1)
    att = []
    for q0 in range(0, s, QUERY_BLOCK):     # bounds the score matrix
        qb = q[:, q0:q0 + QUERY_BLOCK]
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k) / math.sqrt(q.shape[-1])
        causal = (q0 + jnp.arange(qb.shape[1]))[:, None] >= jnp.arange(s)
        p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        att.append(jnp.einsum("bhqk,bkhd->bqhd", p, v))
    return x + _linear(jnp.concatenate(att, axis=1), w["attn/wo"], 2, quant)


def _mlp(h, gate, up, down, quant):
    return _linear(jax.nn.silu(_linear(h, gate, 1, quant))
                   * _linear(h, up, 1, quant), down, 1, quant)


def routing(cfg: dict, router, bias, h, quant=None):
    """Weights ``(..., E)`` of every published routed expert for each
    token of ``h``: zero unless chosen."""
    e, k = cfg["published"]["n_routed_experts"], cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(_linear(h, router, 1, quant))
    _, sel = jax.lax.top_k(s + bias, k)
    chosen = jnp.sum(jax.nn.one_hot(sel, e, dtype=jnp.float32), -2)
    w = s * chosen
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return w * cfg["routed_scaling_factor"]


def _moe(cfg, quant, h, w):
    """Routed experts held here, weighted, plus the shared experts."""
    off, eh = cfg["expert_offset"], cfg["n_routed_experts"]
    weight = routing(cfg, w["router"], w["router_bias"], h, quant)
    y = _mlp(h, w["shared/gate"], w["shared/up"], w["shared/down"], quant)
    for e in range(eh):
        y = y + weight[..., off + e, None] * _mlp(
            h, w["w_gate"][e], w["w_up"][e], w["w_down"][e], quant)
    return y


def _dense_layer(cfg, quant, x, w_attn, w_mlp):
    x = _attention(cfg, quant, x, w_attn)
    h = _rms(x, w_attn["ln2"], cfg["rms_norm_eps"])
    return x + _mlp(h, w_mlp["gate"], w_mlp["up"], w_mlp["down"], quant)


def _moe_layer(cfg, quant, x, w_attn, w_moe):
    x = _attention(cfg, quant, x, w_attn)
    return x + _moe(cfg, quant, _rms(x, w_attn["ln2"], cfg["rms_norm_eps"]),
                    w_moe)


def logits(cfg: dict, seed: int, tokens: np.ndarray, picks: np.ndarray,
           quant: Optional[str] = None):
    """Float32 logits ``(B, n, vocab)`` (on the device) at positions
    ``picks`` (B, n) of the right-padded sequences ``tokens`` (B, S)."""
    with jax.default_matmul_precision("highest"):
        base = wlib.base_key(seed)
        std = cfg["router_bias_std"]

        def maker(shapes):
            @jax.jit
            def make(base, l):
                return _strip({p: wmoe.layer_leaf(base, p, s, l, std)
                               for p, s in shapes.items()})
            return make
        make_attn = maker(attention_shapes(cfg))
        make_dense = maker(dense_shapes(cfg))
        make_moe = maker(moe_shapes(cfg))

        @jax.jit
        def embed(base, tok):
            table = wmoe.draw(wlib.leaf_key(base, "embed"), "embed",
                              (cfg["vocab_size"], cfg["hidden_size"]), std)
            return jnp.take(table, tok, axis=0).astype(jnp.float32)

        dense = jax.jit(lambda x, a, m: _dense_layer(cfg, quant, x, a, m))
        sparse = jax.jit(lambda x, a, m: _moe_layer(cfg, quant, x, a, m))

        @jax.jit
        def head(base, x, picks):
            d, v = cfg["hidden_size"], cfg["vocab_size"]
            fn = wmoe.draw(wlib.leaf_key(base, "final_norm"), "final_norm",
                           (d,), std).astype(jnp.float32)
            w = wmoe.draw(wlib.leaf_key(base, "lm_head"), "lm_head",
                          (d, v), std).astype(jnp.float32)
            xp = jnp.take_along_axis(x, picks[..., None], axis=1)
            return _linear(_rms(xp, fn, cfg["rms_norm_eps"]), w, 1, quant)

        k = cfg["first_k_dense_replace"]
        x = embed(base, jnp.asarray(tokens, jnp.int32))
        for l in range(cfg["num_hidden_layers"]):
            attn = make_attn(base, jnp.int32(l))
            if l < k:
                x = dense(x, attn, make_dense(base, jnp.int32(l)))
            else:
                x = sparse(x, attn, make_moe(base, jnp.int32(l - k)))
        return head(base, x, jnp.asarray(picks, jnp.int32))
