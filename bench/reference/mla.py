"""Plain float32 reference of a decoder with Multi-head Latent Attention
(MiniCPM3 / DeepSeek-V2) and a SwiGLU MLP, in ``jax.numpy`` at the
highest matmul precision. It imports nothing of the program.

One causal forward over whole sequences (prompt and served tokens), no
cache, no batching tricks, one layer at a time: each layer's weights are
made from the seed (``bench/harness/weights.py``) just before it runs,
so the float32 model never has to fit on the chip at once.

Per layer, with ``rms(x, w) = x / sqrt(mean(x^2) + eps) * w``:

    h      = rms(x, ln1)
    q      = rms(h @ wq_a, q_norm) @ wq_b          -> heads x (nope | rope)
    kv     = h @ wkv_a                              -> (latent | k_rope)
    latent = rms(kv[:kv_lora_rank], kv_norm)
    k      = (latent @ wk_b | rope(k_rope), shared by every head)
    v      = latent @ wv_b
    x     += s * (softmax(q k^T / sqrt(nope + rope), causal) v) @ wo
    x     += s * (silu(rms(x, ln2) @ gate) * (rms(x, ln2) @ up)) @ down

with ``s = scale_depth / sqrt(num_hidden_layers)``, RoPE on the rope
halves (rotate-half pairing, ``rope_theta``), embeddings times
``scale_emb`` and the final norm's output divided by ``hidden_size /
dim_model_base`` before ``lm_head``, as MiniCPM3 defines them.

``quant`` makes the control: every linear layer computed from weights
and inputs rounded to ``"int8"`` or ``"fp8"`` (e4m3), with one scale per
output channel and per token, the lower precisions a later change might
be tempted to serve.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import weights as wlib

QUERY_BLOCK = 512          # queries per score matrix



def layer_shapes(cfg: dict) -> dict:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    ff = cfg["intermediate_size"]
    return {"layers/attn/wq_a": (d, rq), "layers/attn/wq_b": (rq, h, dn + dr),
            "layers/attn/wkv_a": (d, rkv + dr),
            "layers/attn/wk_b": (rkv, h, dn), "layers/attn/wv_b": (rkv, h, dv),
            "layers/attn/wo": (h, dv, d), "layers/attn/q_norm": (rq,),
            "layers/attn/kv_norm": (rkv,), "layers/ln1": (d,),
            "layers/ln2": (d,), "layers/mlp/gate": (d, ff),
            "layers/mlp/up": (d, ff), "layers/mlp/down": (ff, d)}


def model_shapes(cfg: dict) -> dict:
    """``{path: shape}`` of the whole model, stacked leaves with the
    layer count first: what the program's weight tree must hold."""
    d, v, n_l = cfg["hidden_size"], cfg["vocab_size"], \
        cfg["num_hidden_layers"]
    out = {p: (n_l,) + s for p, s in layer_shapes(cfg).items()}
    out.update({"embed": (v, d), "final_norm": (d,), "lm_head": (d, v)})
    return out


def _quant(x, axes, kind):
    """``x`` rounded to int8 (symmetric, 127 steps a side) or to fp8
    e4m3 (largest magnitude 448), one scale per slice over ``axes``."""
    top = 127.0 if kind == "int8" else 448.0
    s = jnp.max(jnp.abs(x), axis=axes, keepdims=True) / top
    s = jnp.where(s > 0, s, 1.0)
    if kind == "int8":
        return jnp.round(x / s) * s
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _linear(x, w, n_in, quant):
    """``x`` (..., in) times ``w`` (in..., out...) with ``n_in`` leading
    input axes of ``w`` contracted against x's trailing ones."""
    in_shape, out_shape = w.shape[:n_in], w.shape[n_in:]
    w2 = w.reshape(math.prod(in_shape), math.prod(out_shape))
    x2 = x.reshape(x.shape[:x.ndim - n_in] + (w2.shape[0],))
    if quant is not None:
        w2 = _quant(w2, 0, quant)
        x2 = _quant(x2, -1, quant)
    return (x2 @ w2).reshape(x2.shape[:-1] + out_shape)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: (B, S, ..., D) at positions 0..S-1."""
    d = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    ang = ang.reshape((1, x.shape[1]) + (1,) * (x.ndim - 3) + (d // 2,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(cfg, quant, x, w):
    eps = cfg["rms_norm_eps"]
    s_res = cfg["scale_depth"] / math.sqrt(cfg["num_hidden_layers"])
    rkv, dn = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"]
    w = {k.rsplit("/", 1)[-1]: v.astype(jnp.float32) for k, v in w.items()}
    b, s, _ = x.shape
    h = _rms(x, w["ln1"], eps)
    q = _linear(_rms(_linear(h, w["wq_a"], 1, quant), w["q_norm"], eps),
                w["wq_b"], 1, quant)                       # (B,S,H,dn+dr)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:],
                                            cfg["rope_theta"])], -1)
    kv = _linear(h, w["wkv_a"], 1, quant)
    latent = _rms(kv[..., :rkv], w["kv_norm"], eps)
    k_rope = _rope(kv[..., rkv:], cfg["rope_theta"])      # (B,S,dr)
    k_nope = _linear(latent, w["wk_b"], 1, quant)          # (B,S,H,dn)
    v = _linear(latent, w["wv_b"], 1, quant)               # (B,S,H,dv)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_rope[:, :, None, :], k_nope.shape[:3] + k_rope.shape[-1:])], -1)
    att = []
    for q0 in range(0, s, QUERY_BLOCK):     # bounds the score matrix
        qb = q[:, q0:q0 + QUERY_BLOCK]
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k) / math.sqrt(q.shape[-1])
        causal = (q0 + jnp.arange(qb.shape[1]))[:, None] >= jnp.arange(s)
        p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        att.append(jnp.einsum("bhqk,bkhd->bqhd", p, v))
    att = jnp.concatenate(att, axis=1)
    x = x + s_res * _linear(att, w["wo"], 2, quant)
    h2 = _rms(x, w["ln2"], eps)
    mlp = jax.nn.silu(_linear(h2, w["gate"], 1, quant)) * \
        _linear(h2, w["up"], 1, quant)
    return x + s_res * _linear(mlp, w["down"], 1, quant)


def logits(cfg: dict, seed: int, tokens: np.ndarray, picks: np.ndarray,
           quant: Optional[str] = None):
    """Float32 logits ``(B, n, vocab)`` (on the device) at positions
    ``picks`` (B, n) of the right-padded sequences ``tokens`` (B, S)."""
    with jax.default_matmul_precision("highest"):
        base = wlib.base_key(seed)
        shapes = layer_shapes(cfg)

        @jax.jit
        def make_layer(base, l):
            return {p: wlib.layer_leaf(base, p, s, l)
                    for p, s in shapes.items()}

        @jax.jit
        def embed(base, tok):
            table = wlib.draw(wlib.leaf_key(base, "embed"), "embed",
                              (cfg["vocab_size"], cfg["hidden_size"]))
            return jnp.take(table, tok, axis=0).astype(jnp.float32) \
                * cfg["scale_emb"]

        layer = jax.jit(lambda x, w: _layer(cfg, quant, x, w))

        @jax.jit
        def head(base, x, picks):
            d, v = cfg["hidden_size"], cfg["vocab_size"]
            fn = wlib.draw(wlib.leaf_key(base, "final_norm"), "final_norm",
                           (d,)).astype(jnp.float32)
            w = wlib.draw(wlib.leaf_key(base, "lm_head"), "lm_head",
                          (d, v)).astype(jnp.float32)
            xp = jnp.take_along_axis(x, picks[..., None], axis=1)
            xp = _rms(xp, fn, cfg["rms_norm_eps"]) / (
                d / cfg["dim_model_base"])
            return _linear(xp, w, 1, quant)

        x = embed(base, jnp.asarray(tokens, jnp.int32))
        for l in range(cfg["num_hidden_layers"]):
            x = layer(x, make_layer(base, jnp.int32(l)))
        return head(base, x, jnp.asarray(picks, jnp.int32))
