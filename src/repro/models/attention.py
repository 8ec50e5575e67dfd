"""Attention layers: GQA (optional QKV bias, optional sliding window) and
MLA (Multi-head Latent Attention, MiniCPM3/DeepSeek-style; with a
compressed query, or a direct query projection when ``q_lora_rank`` is 0).

Each layer exposes ``specs(cfg)`` (parameter declarations) and
``apply(cfg, p, x, mode, cache, pos)`` -> (out, new_cache).

Cache layouts (per layer, no leading layers axis here):
  GQA : {"k": (B, S_c, Hkv, D), "v": (B, S_c, Hkv, D)}   S_c = window or seq
  MLA : {"latent": (B, S_c, kv_lora), "k_rope": (B, S_c, rope_dim)}
Cached K is stored *post-RoPE* (standard for ring buffers: relative property
is preserved because Q is rotated at query position). MLA decode attends
over the latent cache in absorbed form; training and prefill expand the
latent into per-head K/V.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ModelConfig
from repro.dist import collectives
from repro.dist.sharding import constrain, mesh_axis_size
from repro.models import common
from repro.models.common import Spec, blockwise_attention, decode_attention, apply_rope


# ---------------------------------------------------------------------------
# slot bookkeeping for (ring) caches
# ---------------------------------------------------------------------------

def cache_slot_positions(cache_len_total: int, size: int, pos) -> jnp.ndarray:
    """Absolute position held by each cache slot, -1 if empty.

    For a full cache (size >= max seq) slot i holds position i (valid iff
    i <= pos). For a ring buffer of ``size`` slots, slot i holds the largest
    p <= pos with p % size == i (valid iff p >= 0); assumes contiguous fill.
    ``pos`` may be a scalar (returns (S,)) or per-row (B,) (returns (B,S) —
    continuous batching, every request at its own position).
    """
    idx = jnp.arange(size, dtype=jnp.int32)
    pos = jnp.asarray(pos, jnp.int32)[..., None]     # () -> (1,), (B,) -> (B,1)
    if cache_len_total <= size:  # full cache
        return jnp.where(idx <= pos, idx, -1)        # (S,) or (B,S)
    p = pos - ((pos - idx) % size)
    return jnp.where(p >= 0, p, -1)


def ring_update(buf: jnp.ndarray, new: jnp.ndarray, pos) -> jnp.ndarray:
    """Write ``new`` (B, 1, ...) at slot pos % size of ``buf`` (B, size, ...).

    ``pos`` scalar writes one slot for the whole batch; per-row (B,) writes
    each row at its own slot (ragged continuous batching).
    """
    size = buf.shape[1]
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 0:
        start = (jnp.zeros((), jnp.int32), jax.lax.rem(pos, size)) \
            + (jnp.zeros((), jnp.int32),) * (buf.ndim - 2)
        return jax.lax.dynamic_update_slice(buf, new.astype(buf.dtype), start)
    slot = jax.lax.rem(pos, size)                            # (B,)
    hit = jnp.arange(size, dtype=jnp.int32)[None, :] == slot[:, None]
    hit = hit.reshape(hit.shape + (1,) * (buf.ndim - 2))
    return jnp.where(hit, new.astype(buf.dtype), buf)


def paged_decode_attention(q, k_pool, v_pool, page_table, k_positions, pos,
                           k_scale_pool=None, v_scale_pool=None):
    """Single-token attention reading one layer's K/V through a page table.

    ``k_pool``/``v_pool`` are page pools ``(n_pool, page, Hkv, D)`` (one
    layer of a ``registry.PagedStateStore`` state); ``page_table`` is the
    per-row table ``(B, pages_per_row)`` with -1 marking unallocated
    pages. The pools are gathered back to the dense per-row layout and
    handed to :func:`repro.models.common.decode_attention` unchanged, so
    the paged read is bit-identical to the dense one: junk gathered from
    unallocated (-1 -> clamped) entries sits at positions the
    ``k_positions``/``pos`` mask sends to NEG_INF before the softmax.
    Quantized (int8) pools pass their scale pools the same way.
    """
    from repro.kernels.paged_attn import gather_pages
    k = gather_pages(k_pool, page_table)
    v = gather_pages(v_pool, page_table)
    ks = None if k_scale_pool is None else gather_pages(k_scale_pool, page_table)
    vs = None if v_scale_pool is None else gather_pages(v_scale_pool, page_table)
    return decode_attention(q, k, v, k_positions, pos, ks, vs)


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------

def gqa_specs(cfg: ModelConfig) -> Dict[str, Spec]:
    d, h, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    s = {
        "wq": Spec((d, h, hd), ("embed", "heads", "head_dim")),
        "wk": Spec((d, hkv, hd), ("embed", "kv_heads", "head_dim")),
        "wv": Spec((d, hkv, hd), ("embed", "kv_heads", "head_dim")),
        "wo": Spec((h, hd, d), ("heads", "head_dim", "embed")),
    }
    if cfg.qkv_bias:
        s["bq"] = Spec((h, hd), ("heads", "head_dim"), init="zeros")
        s["bk"] = Spec((hkv, hd), ("kv_heads", "head_dim"), init="zeros")
        s["bv"] = Spec((hkv, hd), ("kv_heads", "head_dim"), init="zeros")
    return s


def gqa_apply(cfg: ModelConfig, p, x: jnp.ndarray, mode: str,
              cache: Optional[dict], pos, cache_len_total: int,
              ) -> Tuple[jnp.ndarray, Optional[dict]]:
    b, s, _ = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = constrain(q, "batch", None, "heads", None)
    k = constrain(k, "batch", None, "kv_heads", None)
    v = constrain(v, "batch", None, "kv_heads", None)

    if mode == "decode":
        pos_bt = jnp.broadcast_to(jnp.asarray(pos, jnp.int32)[..., None],
                                  (b, 1))            # scalar or per-row (B,)
        q = apply_rope(q, pos_bt, cfg.rope_theta)
        k = apply_rope(k, pos_bt, cfg.rope_theta)
        size = cache["k"].shape[1]
        cache_sp = ("batch", "kv_seq", "kv_heads", None)
        storage = collectives.current_kv_storage()
        if storage == "int8":
            # int8-resident cache: quantize the new token's K/V per
            # position along the feature axis (blocks never span
            # positions, so a slot write touches only its own scales) and
            # store s8 values + f32 scales; decode_attention dequantizes
            # per block at read time.
            k, k_sc = collectives.quantize_int8_lastdim(k)
            v, v_sc = collectives.quantize_int8_lastdim(v)
            k_scale = constrain(ring_update(cache["k_scale"], k_sc, pos),
                                *cache_sp)
            v_scale = constrain(ring_update(cache["v_scale"], v_sc, pos),
                                *cache_sp)
        elif storage == "f8":
            # f8-resident cache: scale-free e4m3 cast of the new token's
            # K/V (no companion scale leaves; decode_attention upcasts per
            # block at read time).
            k = collectives.cast_f8(k)
            v = collectives.cast_f8(v)
        k_cache = constrain(ring_update(cache["k"], k, pos), *cache_sp)
        v_cache = constrain(ring_update(cache["v"], v, pos), *cache_sp)
        kpos = cache_slot_positions(cache_len_total + 1, size, pos)
        if cfg.attn_window:
            win_lo = jnp.asarray(pos, jnp.int32)[..., None] - cfg.attn_window
            kpos = jnp.where(kpos > win_lo, kpos, -1)
        # serve_sp: the cache is sequence-sharded; attention needs every
        # slot, so this is decode's activation all-gather (s8 under
        # act_transport="int8"). Gather to a head-replicated layout — a
        # pure all-gather over the sequence shards; the scores einsum then
        # slices heads locally against the head-sharded q. Under
        # serve_decode the cache is batch-resident and these constraints
        # move nothing. An int8-*resident* cache passes through the gather
        # as s8 (already compressed); its f32 scales reshard raw — they
        # are 1/block of the payload.
        gather_sp = ("batch", None, None, None)
        k_att = collectives.act_gather(k_cache, *gather_sp)
        v_att = collectives.act_gather(v_cache, *gather_sp)
        if storage == "int8":
            out = decode_attention(q, k_att, v_att, kpos, pos,
                                   k_scale=constrain(k_scale, *gather_sp),
                                   v_scale=constrain(v_scale, *gather_sp))
            new_cache = {"k": k_cache, "v": v_cache,
                         "k_scale": k_scale, "v_scale": v_scale}
        else:
            out = decode_attention(q, k_att, v_att, kpos, pos)
            new_cache = {"k": k_cache, "v": v_cache}
    else:
        positions = jnp.arange(s, dtype=jnp.int32)[None, :]
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
        new_cache = None
        if mode == "prefill":
            size = cfg.attn_window or s
            new_cache = {"k": k[:, -size:].astype(common.COMPUTE_DTYPE),
                         "v": v[:, -size:].astype(common.COMPUTE_DTYPE)}
        # TP > kv_heads: replicate KV across query-head groups so attention
        # activations stay head-sharded (MaxText-style KV replication).
        tp = mesh_axis_size("model")
        h, hkv = cfg.n_heads, cfg.n_kv_heads
        if tp > 1 and h % tp == 0 and hkv % tp != 0:
            rep = h // hkv
            k = constrain(jnp.repeat(k, rep, axis=2), "batch", None, "heads", None)
            v = constrain(jnp.repeat(v, rep, axis=2), "batch", None, "heads", None)
        out = blockwise_attention(q, k, v, causal=cfg.causal,
                                  window=cfg.attn_window)
    y = constrain(jnp.einsum("bshk,hkd->bsd", out, p["wo"]),
                  "batch", None, "act_embed")
    return y, new_cache


def gqa_cache_shape(cfg: ModelConfig, batch: int, seq: int):
    size = min(cfg.attn_window, seq) if cfg.attn_window else seq
    kv = (batch, size, cfg.n_kv_heads, cfg.head_dim)
    return {"k": kv, "v": kv}


def gqa_cache_axes():
    """Logical axes of the GQA ring-buffer cache leaves (this family's
    contribution to the StateStore protocol; the stack prepends its
    "layers" axis). ``kv_seq`` marks the slice-admission axis — a
    windowed (ring) cache still carries it, but slot streaming admits it
    whole-row after an exact-length prefill."""
    kv = ("batch", "kv_seq", "kv_heads", "head_dim")
    return {"k": kv, "v": kv}


# ---------------------------------------------------------------------------
# MLA (latent KV cache)
# ---------------------------------------------------------------------------

def mla_specs(cfg: ModelConfig) -> Dict[str, Spec]:
    d, h = cfg.d_model, cfg.n_heads
    rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.nope_head_dim, cfg.rope_head_dim, cfg.v_head_dim
    s = {
        "wkv_a": Spec((d, rkv + dr), ("embed", "kv_lora")),
        "wk_b": Spec((rkv, h, dn), ("kv_lora", "heads", "head_dim")),
        "wv_b": Spec((rkv, h, dv), ("kv_lora", "heads", "head_dim")),
        "wo": Spec((h, dv, d), ("heads", "head_dim", "embed")),
        "kv_norm": Spec((rkv,), ("kv_lora",), init="ones"),
    }
    if rq:
        s["wq_a"] = Spec((d, rq), ("embed", "q_lora"))
        s["wq_b"] = Spec((rq, h, dn + dr), ("q_lora", "heads", "head_dim"))
        s["q_norm"] = Spec((rq,), ("q_lora",), init="ones")
    else:       # no query compression: a direct projection, no q_norm
        s["wq"] = Spec((d, h, dn + dr), ("embed", "heads", "head_dim"))
    return s


def _mla_qk(cfg, p, x, positions):
    """Project to per-head q (nope|rope) and latent kv. x:(B,S,d)."""
    dn, dr = cfg.nope_head_dim, cfg.rope_head_dim
    if cfg.q_lora_rank:
        cq = common.rms_norm(jnp.einsum("bsd,dr->bsr", x, p["wq_a"]),
                             p["q_norm"], cfg.norm_eps)
        q = jnp.einsum("bsr,rhk->bshk", cq, p["wq_b"])      # (B,S,H,dn+dr)
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    kv = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"])           # (B,S,rkv+dr)
    latent = common.rms_norm(kv[..., :cfg.kv_lora_rank], p["kv_norm"],
                             cfg.norm_eps)
    k_rope = apply_rope(kv[..., None, cfg.kv_lora_rank:], positions,
                        cfg.rope_theta)[..., 0, :]          # (B,S,dr) shared
    return jnp.concatenate([q_nope, q_rope], -1), latent, k_rope


def _mla_expand(cfg, p, latent, k_rope):
    """Expand latent into per-head K (nope|rope-shared) and V."""
    k_nope = jnp.einsum("bsr,rhk->bshk", latent, p["wk_b"])
    v = jnp.einsum("bsr,rhk->bshk", latent, p["wv_b"])
    kr = jnp.broadcast_to(k_rope[:, :, None, :],
                          k_nope.shape[:3] + (cfg.rope_head_dim,))
    return jnp.concatenate([k_nope, kr], -1), v


def _mla_decode_attention(cfg, p, q, latent, k_rope, k_positions, pos):
    """Single-token MLA attention in absorbed (latent) form.

    The same attention as expanding the cache with :func:`_mla_expand`
    and calling ``decode_attention``, in another order (DeepSeek-V2's
    inference form): ``wk_b`` folds into the query, scores are taken
    against the latent and the shared rope key, and ``wv_b`` applies
    after the weighted sum of latents. The cache is read as it is stored,
    and no per-head K or V exists. Operands keep their dtype, products
    accumulate in float32, and the absorbed query ``q_lat`` and output
    ``o_lat`` stay float32. The scale is the per-head query width's.

    q:(B,1,H,dn+dr), latent:(B,S,rkv), k_rope:(B,S,dr) -> (B,1,H,dv).
    """
    b, _, _, d = q.shape
    f32 = jnp.float32
    q_nope = q[:, 0, :, :cfg.nope_head_dim]                     # (B,H,dn)
    q_rope = q[:, 0, :, cfg.nope_head_dim:]                     # (B,H,dr)
    q_lat = constrain(jnp.einsum("bhk,rhk->bhr", q_nope, p["wk_b"],
                                 preferred_element_type=f32),
                      "batch", "heads", None)                   # (B,H,rkv)
    s = (jnp.einsum("bhr,bsr->bhs", q_lat, latent,
                    preferred_element_type=f32)
         + jnp.einsum("bhk,bsk->bhs", q_rope, k_rope,
                      preferred_element_type=f32)) * (1.0 / np.sqrt(d))
    valid = common.decode_mask(k_positions, pos, b, latent.shape[1])
    s = jnp.where(valid[:, None, :], s, common.NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    o_lat = constrain(jnp.einsum("bhs,bsr->bhr", w, latent,
                                 preferred_element_type=f32),
                      "batch", "heads", None)                   # (B,H,rkv)
    out = jnp.einsum("bhr,rhv->bhv", o_lat, p["wv_b"],
                     preferred_element_type=f32)
    return out[:, None].astype(q.dtype)


def mla_apply(cfg: ModelConfig, p, x, mode, cache, pos, cache_len_total):
    """MLA block. Decode attends over the latent cache in absorbed form
    (:func:`_mla_decode_attention`); training and prefill expand the latent
    into per-head K/V (:func:`_mla_expand`), the cheaper form over a whole
    sequence."""
    b, s, _ = x.shape
    if mode == "decode":
        positions = jnp.broadcast_to(jnp.asarray(pos, jnp.int32)[..., None],
                                     (b, 1))
        q, latent, k_rope = _mla_qk(cfg, p, x, positions)
        storage = collectives.current_kv_storage()
        kr_new = k_rope[:, :, None, :]
        if storage == "f8":
            # f8-resident latent cache: scale-free e4m3, upcast at the
            # same read-time boundary as int8 (the latent attention)
            latent = collectives.cast_f8(latent)
            kr_new = collectives.cast_f8(kr_new)
        if storage == "int8":
            # int8-resident latent cache (MLA's read-time boundary is the
            # latent attention, so dequantization happens just before
            # _mla_decode_attention instead of inside decode_attention)
            latent, lat_sc = collectives.quantize_int8_lastdim(latent)
            kr_new, kr_sc = collectives.quantize_int8_lastdim(kr_new)
            lat_scale = constrain(ring_update(cache["latent_scale"], lat_sc,
                                              pos), "batch", "kv_seq", None)
            kr_scale = constrain(ring_update(cache["k_rope_scale"], kr_sc,
                                             pos), "batch", "kv_seq", None,
                                  None)
        lat_cache = constrain(ring_update(cache["latent"], latent, pos),
                              "batch", "kv_seq", None)
        kr_cache = constrain(ring_update(cache["k_rope"], kr_new, pos),
                             "batch", "kv_seq", None, None)
        # decode's activation all-gather (MLA form): the latent cache is
        # the compressed KV state — gather it (s8 under int8 transport, or
        # natively s8 when int8-resident) before the latent attention.
        lat_att = collectives.act_gather(lat_cache, "batch", None, None)
        kr_att = collectives.act_gather(kr_cache, "batch", None, None, None)
        if storage == "int8":
            lat_att = collectives.dequantize_int8_lastdim(
                lat_att, constrain(lat_scale, "batch", None, None))
            kr_att = collectives.dequantize_int8_lastdim(
                kr_att, constrain(kr_scale, "batch", None, None, None))
            lat_att = lat_att.astype(x.dtype)
            kr_att = kr_att.astype(x.dtype)
        elif storage == "f8":
            lat_att = collectives.uncast_f8(lat_att, x.dtype)
            kr_att = collectives.uncast_f8(kr_att, x.dtype)
        kpos = cache_slot_positions(cache_len_total + 1, lat_cache.shape[1], pos)
        out = _mla_decode_attention(cfg, p, q, lat_att, kr_att[..., 0, :],
                                    kpos, pos)
        new_cache = {"latent": lat_cache, "k_rope": kr_cache}
        if storage == "int8":
            new_cache["latent_scale"] = lat_scale
            new_cache["k_rope_scale"] = kr_scale
    else:
        positions = jnp.arange(s, dtype=jnp.int32)[None, :]
        q, latent, k_rope = _mla_qk(cfg, p, x, positions)
        k, v = _mla_expand(cfg, p, latent, k_rope)
        out = blockwise_attention(q, k, v, causal=cfg.causal)
        new_cache = None
        if mode == "prefill":
            new_cache = {"latent": latent.astype(common.COMPUTE_DTYPE),
                         "k_rope": k_rope[:, :, None, :].astype(common.COMPUTE_DTYPE)}
    y = constrain(jnp.einsum("bshk,hkd->bsd", out, p["wo"]),
                  "batch", None, "act_embed")
    return y, new_cache


def mla_cache_shape(cfg: ModelConfig, batch: int, seq: int):
    return {"latent": (batch, seq, cfg.kv_lora_rank),
            "k_rope": (batch, seq, 1, cfg.rope_head_dim)}


def mla_cache_axes():
    """Logical axes of the MLA latent-cache leaves (StateStore protocol
    contribution; the stack prepends its "layers" axis)."""
    return {"latent": ("batch", "kv_seq", "kv_lora"),
            "k_rope": ("batch", "kv_seq", None, None)}
