"""Flash-decode: single-token GQA attention against a long KV cache.

Grid: (B, num_kv_blocks), kv dimension sequential with online-softmax state
in VMEM scratch. Per-sequence valid lengths ride in scalar-prefetch SMEM —
ragged cache fill is masked inside the kernel, so one batched call serves
requests at different positions (continuous batching).

Per step VMEM: q (H, D) + k,v (bk, Hkv, D) + acc (H, D) f32; with bk = 512,
Hkv <= 16, D <= 192 this stays ~1-2 MB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30
DEFAULT_BLOCK_K = 512


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                   m_ref, l_ref, acc_ref, *,
                   scale: float, block_k: int, num_kv_blocks: int,
                   group: int):
    b = pl.program_id(0)
    ik = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                        # (H, D)
    k = k_ref[0].astype(jnp.float32)                        # (bk, Hkv, D)
    v = v_ref[0].astype(jnp.float32)
    h, d = q.shape
    hkv = k.shape[1]
    # expand kv heads to query heads via index arithmetic (no materialized
    # repeat: dot per kv-head group)
    qg = q.reshape(hkv, group, d)
    s = jax.lax.dot_general(qg, k.transpose(1, 2, 0),
                            (((2,), (1,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32) * scale
    s = s.reshape(h, k.shape[0])                            # (H, bk)

    kv_pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    valid = kv_pos < len_ref[b]
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[:, :1]
    l_prev = l_ref[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                                  # (H, bk)
    l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
    pv = jax.lax.dot_general(
        p.reshape(hkv, group, -1), v.transpose(1, 0, 2),
        (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32).reshape(h, d)
    acc_ref[...] = acc_ref[...] * alpha + pv
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ik == num_kv_blocks - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[:, :1], 1e-30)
                    ).astype(o_ref.dtype)


def decode_attention_kernel(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                            lengths: jnp.ndarray, *,
                            block_k: int = DEFAULT_BLOCK_K,
                            interpret: bool = False) -> jnp.ndarray:
    """q: (B, H, D); k, v: (B, S, Hkv, D); lengths: (B,) -> (B, H, D)."""
    b, h, d = q.shape
    s, hkv = k.shape[1], k.shape[2]
    group = h // hkv
    bk = min(block_k, s)
    assert s % bk == 0
    nk = s // bk
    scale = 1.0 / (d ** 0.5)

    kernel = functools.partial(_decode_kernel, scale=scale, block_k=bk,
                               num_kv_blocks=nk, group=group)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, nk),
        in_specs=[
            pl.BlockSpec((1, h, d), lambda ib, ik, len_ref: (ib, 0, 0)),
            pl.BlockSpec((1, bk, hkv, d),
                         lambda ib, ik, len_ref: (ib, ik, 0, 0)),
            pl.BlockSpec((1, bk, hkv, d),
                         lambda ib, ik, len_ref: (ib, ik, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, d), lambda ib, ik, len_ref: (ib, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, 128), jnp.float32),
            pltpu.VMEM((h, 128), jnp.float32),
            pltpu.VMEM((h, d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(lengths, q, k, v)
