"""Shared modeling primitives: parameter-spec machinery, norms, RoPE,
embeddings, blockwise (memory-efficient) attention, losses.

Parameters are plain pytrees of jnp arrays. Every parameter leaf is declared
through a ``Spec`` carrying its shape, dtype and *logical axis names*; the
dist layer maps logical axes onto mesh axes. Layer stacks are stored with a
leading ``layers`` axis and consumed with ``lax.scan`` (homogeneous stacks)
so HLO size is O(1) in depth.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.dist import collectives
from repro.dist.sharding import constrain

PyTree = Any

DEFAULT_PARAM_DTYPE = jnp.bfloat16
COMPUTE_DTYPE = jnp.bfloat16


@dataclasses.dataclass(frozen=True)
class Spec:
    """Declaration of one parameter leaf."""
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]   # logical axis names, len == len(shape)
    dtype: Any = None                 # None -> DEFAULT_PARAM_DTYPE
    init: str = "normal"              # "normal" | "zeros" | "ones" | "small"

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def materialize(spec: Spec, key) -> jnp.ndarray:
    dtype = spec.dtype or DEFAULT_PARAM_DTYPE
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, dtype)
    # fan-in scaled normal; last axis treated as fan-out
    fan_in = int(np.prod(spec.shape[:-1])) if len(spec.shape) > 1 else spec.shape[0]
    scale = 0.02 if spec.init == "small" else 1.0 / np.sqrt(max(fan_in, 1))
    return (jax.random.normal(key, spec.shape, jnp.float32) * scale).astype(dtype)


def tree_init(specs: PyTree, key) -> PyTree:
    leaves, treedef = jax.tree.flatten(specs, is_leaf=lambda x: isinstance(x, Spec))
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(treedef, [materialize(s, k) for s, k in zip(leaves, keys)])


def tree_abstract(specs: PyTree) -> PyTree:
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype or DEFAULT_PARAM_DTYPE),
        specs, is_leaf=lambda x: isinstance(x, Spec))


def tree_axes(specs: PyTree) -> PyTree:
    return jax.tree.map(lambda s: s.axes, specs, is_leaf=lambda x: isinstance(x, Spec))


def stack_layer_specs(layer_specs: PyTree, n_layers: int) -> PyTree:
    """Add a leading ``layers`` axis to every leaf spec."""
    return jax.tree.map(
        lambda s: Spec((n_layers,) + s.shape, ("layers",) + s.axes, s.dtype, s.init),
        layer_specs, is_leaf=lambda x: isinstance(x, Spec))


# ---------------------------------------------------------------------------
# primitives
# ---------------------------------------------------------------------------

def rms_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(dt) * scale.astype(dt)


def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (..., S, H, D); positions: broadcastable to (..., S)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                      # (D/2,)
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (..., S, D/2)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def swiglu(x, w_gate, w_up, w_down):
    hid_axes = (None,) * (x.ndim - 1) + ("mlp",)
    hid_axes = ("batch",) + hid_axes[1:]
    g = constrain(jnp.einsum("...d,df->...f", x, w_gate), *hid_axes)
    u = constrain(jnp.einsum("...d,df->...f", x, w_up), *hid_axes)
    return jnp.einsum("...f,fd->...d", jax.nn.silu(g) * u, w_down)


# ---------------------------------------------------------------------------
# attention (XLA path): blockwise online-softmax, never materializes S x S
# ---------------------------------------------------------------------------

NEG_INF = -1e30


def _attn_block(q, k, v, q_pos, k_pos, causal, window, scale):
    """One (q-block, kv-block) tile. q:(B,bq,H,D) k/v:(B,bk,Hkv,D)."""
    b, bq, h, d = q.shape
    hkv = k.shape[2]
    group = h // hkv
    qg = q.reshape(b, bq, hkv, group, d)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    mask = jnp.ones((bq, k.shape[1]), bool)
    if causal:
        mask &= q_pos[:, None] >= k_pos[None, :]
    if window:
        mask &= q_pos[:, None] - k_pos[None, :] < window
    mask &= (k_pos >= 0)[None, :]
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1)                                   # (B,hkv,g,bq)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bhgqd", p, v.astype(jnp.float32))
    return m, l, o


def blockwise_attention(q, k, v, *, causal=True, window=0,
                        q_offset=0, k_positions=None,
                        block_q=1024, block_k=1024):
    """Memory-efficient attention.

    q: (B, Sq, H, D); k,v: (B, Sk, Hkv, D). Returns (B, Sq, H, D).
    ``q_offset``: absolute position of q[0] (for decode/prefill continuation).
    ``k_positions``: optional (Sk,) absolute positions of cache slots
      (ring buffers); -1 marks invalid slots. Defaults to arange(Sk).
    """
    b, sq, h, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    scale = 1.0 / np.sqrt(d)
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    nq, nk = sq // bq, sk // bk
    assert sq % bq == 0 and sk % bk == 0, (sq, bq, sk, bk)
    if k_positions is None:
        k_positions = jnp.arange(sk, dtype=jnp.int32)
    q_pos = q_offset + jnp.arange(sq, dtype=jnp.int32)

    dv = v.shape[-1]
    qb = constrain(q.reshape(b, nq, bq, h, d).transpose(1, 0, 2, 3, 4),
                   None, "batch", None, "heads", None)
    kb = constrain(k.reshape(b, nk, bk, hkv, d).transpose(1, 0, 2, 3, 4),
                   None, "batch", None, "kv_heads", None)
    vb = constrain(v.reshape(b, nk, bk, hkv, dv).transpose(1, 0, 2, 3, 4),
                   None, "batch", None, "kv_heads", None)
    qpb = q_pos.reshape(nq, bq)
    kpb = k_positions.reshape(nk, bk)
    run_axes = ("batch", "kv_heads", None, None)

    def q_step(_, qi):
        qblk, qp = qi
        group = h // hkv

        def kv_step(carry, ki):
            m_run, l_run, o_run = carry
            kblk, vblk, kp = ki
            m, l, o = _attn_block(qblk, kblk, vblk, qp, kp, causal, window, scale)
            m_new = jnp.maximum(m_run, m)
            a_old = jnp.exp(m_run - m_new)
            a_new = jnp.exp(m - m_new)
            l_new = l_run * a_old + l * a_new
            o_new = o_run * a_old[..., None] + o * a_new[..., None]
            return (constrain(m_new, *run_axes), constrain(l_new, *run_axes),
                    constrain(o_new, *run_axes, None)), None

        m0 = constrain(jnp.full((b, hkv, group, bq), NEG_INF, jnp.float32),
                       *run_axes)
        l0 = constrain(jnp.zeros((b, hkv, group, bq), jnp.float32), *run_axes)
        o0 = constrain(jnp.zeros((b, hkv, group, bq, dv), jnp.float32),
                       *run_axes, None)
        (m_f, l_f, o_f), _ = jax.lax.scan(kv_step, (m0, l0, o0), (kb, vb, kpb))
        out = o_f / jnp.maximum(l_f[..., None], 1e-30)
        out = out.transpose(0, 3, 1, 2, 4).reshape(b, bq, h, dv)
        return None, constrain(out.astype(q.dtype), "batch", None, "heads", None)

    if nq == 1:
        _, out = q_step(None, (qb[0], qpb[0]))
        return out
    _, outs = jax.lax.scan(q_step, None, (qb, qpb))
    return outs.transpose(1, 0, 2, 3, 4).reshape(b, sq, h, dv)


def decode_mask(k_positions, pos, b: int, s: int) -> jnp.ndarray:
    """(B, S) mask of the cache slots a single decode token attends to.

    ``k_positions``: (S,) or per-row (B,S) absolute slot positions (-1
    invalid); ``pos``: scalar or per-row (B,) current position. A slot is
    valid when it holds a position and that position is not past the
    row's own."""
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    kp = jnp.asarray(k_positions, jnp.int32)
    if kp.ndim == 1:
        kp = kp[None, :]
    valid = (kp >= 0) & (kp <= pos_b[:, None])          # (B or 1, S) -> (B,S)
    return jnp.broadcast_to(valid, (b, s))


def decode_attention(q, k_cache, v_cache, k_positions, pos,
                     k_scale=None, v_scale=None):
    """Single-token attention against a cache. q:(B,1,H,D), caches (B,S,Hkv,D).

    ``k_positions``: (S,) or per-row (B,S) absolute slot positions (-1
    invalid); ``pos``: scalar or per-row (B,) current position. Per-row
    forms are the continuous-batching case — every request sits at its own
    position and padded/stale slots are masked row-wise.

    ``k_scale``/``v_scale`` (B,S,Hkv,nb) mark an int8-*resident* cache
    (``kv_storage="int8"``): the stored leaves are blockwise-s8 along the
    feature axis and are dequantized here, per block, at read time — HBM
    holds half the bytes and only the attention operands ever exist in
    float. An f8-resident cache (``kv_storage="f8"``, scale-free e4m3)
    arrives without scales and is upcast here the same way — per block on
    the Pallas kernel path, whole-operand under XLA.
    """
    if k_scale is not None:
        k_cache = collectives.dequantize_int8_lastdim(k_cache, k_scale)
        v_cache = collectives.dequantize_int8_lastdim(v_cache, v_scale)
    elif k_cache.dtype == collectives.F8_DTYPE:
        k_cache = collectives.uncast_f8(k_cache)
        v_cache = collectives.uncast_f8(v_cache)
    b, _, h, d = q.shape
    hkv = k_cache.shape[2]
    dv = v_cache.shape[-1]
    group = h // hkv
    scale = 1.0 / np.sqrt(d)
    qg = q.reshape(b, hkv, group, d).astype(jnp.float32)
    s = jnp.einsum("bhgd,bshd->bhgs", qg, k_cache.astype(jnp.float32)) * scale
    valid = decode_mask(k_positions, pos, b, k_cache.shape[1])
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    s = constrain(s, "batch", "kv_heads", None, "kv_seq")
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgs,bshd->bhgd", p, v_cache.astype(jnp.float32))
    return o.reshape(b, 1, h, dv).astype(q.dtype)


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------

def softmax_xent(logits: jnp.ndarray, labels: jnp.ndarray,
                 mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Mean cross-entropy over (optionally masked) positions. fp32 internals."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if mask is not None:
        mask = mask.astype(jnp.float32)
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
