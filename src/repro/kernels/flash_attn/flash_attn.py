"""Causal GQA flash attention (training/prefill), Pallas TPU.

Grid: (B*H, num_q_blocks, num_kv_blocks) with the kv dimension sequential
("arbitrary") so the online-softmax state lives in VMEM scratch across kv
steps. GQA is expressed in the K/V BlockSpec index maps (query head h reads
kv head h // group) — no KV replication materializes, unlike the XLA path.

VMEM working set per step: q (bq, D) + k,v (bk, D) + acc (bq, D) f32 +
m/l (bq, 128) f32; with bq = bk = 512 and D <= 192 this is ~1.5 MB, well
under the ~16 MB v5e VMEM budget, and all matmul dims are multiples of 128
(MXU-aligned).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, window: int,
                  block_q: int, block_k: int, num_kv_blocks: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)                     # (bq, D)
    k = k_ref[0, 0].astype(jnp.float32)                     # (bk, D)
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    q_pos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    k_pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = jnp.ones_like(s, dtype=jnp.bool_)
    if causal:
        mask &= q_pos >= k_pos
    if window:
        mask &= (q_pos - k_pos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[:, :1]                                   # (bq, 1)
    l_prev = l_ref[:, :1]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ik == num_kv_blocks - 1)
    def _finish():
        l = l_ref[:, :1]
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l, 1e-30)
                       ).astype(o_ref.dtype)


def flash_attention_kernel(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                           *, causal: bool = True, window: int = 0,
                           block_q: int = DEFAULT_BLOCK_Q,
                           block_k: int = DEFAULT_BLOCK_K,
                           interpret: bool = False) -> jnp.ndarray:
    """q: (B, H, S, D); k, v: (B, Hkv, S, D) -> (B, H, S, D)."""
    b, h, s, d = q.shape
    hkv = k.shape[1]
    group = h // hkv
    bq = min(block_q, s)
    bk = min(block_k, s)
    assert s % bq == 0 and s % bk == 0
    nq, nk = s // bq, s // bk
    scale = 1.0 / (d ** 0.5)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        block_q=bq, block_k=bk, num_kv_blocks=nk)

    return pl.pallas_call(
        kernel,
        grid=(b * h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d),
                         lambda bh, iq, ik: (bh // h, bh % h, iq, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bh, iq, ik: (bh // h, (bh % h) // group, ik, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bh, iq, ik: (bh // h, (bh % h) // group, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d),
                               lambda bh, iq, ik: (bh // h, bh % h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),   # m
            pltpu.VMEM((bq, 128), jnp.float32),   # l
            pltpu.VMEM((bq, d), jnp.float32),     # acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
