"""MLA decode attends over the latent cache in absorbed form.

``attention.mla_apply`` in decode mode folds the key up-projection into
the query and applies the value up-projection after the weighted sum of
latents, so no per-head K or V is built. These tests hold it to the
expanded form (``_mla_expand`` + ``common.decode_attention``, the form
prefill still uses) in float32 for every cache storage arm, and check
that a lowered decode step holds no expanded K/V at all."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.dist import collectives
from repro.models import attention, common, transformer
from repro.train import step as step_lib

CFG = smoke_config("minicpm3-4b")
B, S = 3, 12

# per-row (or scalar) current position; the cache holds S slots
POSITIONS = {
    "scalar": 7,
    # row 0 sees only its first slot; slots past each row's position hold
    # stale entries of an earlier request and padding
    "ragged": [0, 5, 11],
    # positions past the cache size: the slots wrap (ring arithmetic of
    # cache_slot_positions) and every slot is attended
    "wrapped": [13, 20, 12],
}


def _f32_layer_params():
    params = common.tree_init(attention.mla_specs(CFG), jax.random.PRNGKey(3))
    params = jax.tree.map(lambda t: t.astype(jnp.float32), params)
    # non-trivial norm scales, so the latent's scale is not all ones
    params["kv_norm"] = 1.0 + 0.3 * jax.random.normal(
        jax.random.PRNGKey(4), params["kv_norm"].shape)
    return params


def _cache(storage, key):
    k1, k2 = jax.random.split(key)
    cache = {"latent": jax.random.normal(k1, (B, S, CFG.kv_lora_rank)),
             "k_rope": jax.random.normal(k2, (B, S, 1, CFG.rope_head_dim))}
    return transformer.quantize_cache(cache, storage)


def _read(cache, name):
    """A cache leaf as float32, dequantized as the decode step reads it."""
    leaf = cache[name]
    if name + "_scale" in cache:
        return collectives.dequantize_int8_lastdim(leaf, cache[name + "_scale"])
    return leaf.astype(jnp.float32)


def _expanded_reference(p, x, new_cache, pos):
    """The expanded form: per-head K/V from the written cache, then the
    shared single-token attention and the output projection."""
    positions = jnp.broadcast_to(jnp.asarray(pos, jnp.int32)[..., None], (B, 1))
    q, _, _ = attention._mla_qk(CFG, p, x, positions)
    k, v = attention._mla_expand(CFG, p, _read(new_cache, "latent"),
                                 _read(new_cache, "k_rope")[..., 0, :])
    kpos = attention.cache_slot_positions(S + 1, S, pos)
    out = common.decode_attention(q, k, v, kpos, pos)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


@pytest.mark.parametrize("positions", sorted(POSITIONS))
@pytest.mark.parametrize("storage", collectives.KV_STORAGES)
def test_absorbed_decode_matches_expanded(storage, positions):
    pos = jnp.asarray(POSITIONS[positions], jnp.int32)
    p = _f32_layer_params()
    x = jax.random.normal(jax.random.PRNGKey(5), (B, 1, CFG.d_model))
    cache = _cache(storage, jax.random.PRNGKey(6))

    def decode(c):
        with collectives.kv_storage_scope(storage):
            return attention.mla_apply(CFG, p, x, "decode", c, pos, S)

    out, new_cache = jax.jit(decode)(cache)
    assert jax.tree.structure(new_cache) == jax.tree.structure(cache)
    assert out.dtype == jnp.float32
    ref = _expanded_reference(p, x, new_cache, pos)
    err = float(jnp.max(jnp.abs(out - ref)))
    scale = float(jnp.max(jnp.abs(ref)))
    assert err <= 1e-5 * scale, (err, scale)

    # masked slots are never read: other stale entries past each row's
    # position leave the output bit-identical
    kpos = attention.cache_slot_positions(S + 1, S, pos)
    masked = ~common.decode_mask(kpos, pos, B, S)
    if positions == "wrapped":
        assert not bool(masked.any())
        return
    assert bool(masked.any())
    other = _cache(storage, jax.random.PRNGKey(7))
    stale = jax.tree.map(
        lambda a, b: jnp.where(masked.reshape((B, S) + (1,) * (a.ndim - 2)),
                               b, a), cache, other)
    out_stale, _ = jax.jit(decode)(stale)
    np.testing.assert_array_equal(np.asarray(out_stale), np.asarray(out))


def _shapes(lowered_text):
    """Shapes of every tensor value in StableHLO text, as int tuples."""
    return {tuple(int(d) for d in m.rstrip("x").split("x"))
            for m in re.findall(r"tensor<((?:\d+x)+)", lowered_text)}


@pytest.mark.parametrize("storage", collectives.KV_STORAGES)
def test_decode_step_holds_no_expanded_kv(storage):
    """Engagement: no value of a lowered decode step has the expanded
    K (B,T,H,dn+dr) or V (B,T,H,dv) shape, in any axis order. Prefill,
    which expands, is the control that the check can see them."""
    b, t = 3, 24
    params = jax.eval_shape(
        lambda: transformer.init_params(CFG, jax.random.PRNGKey(0)))
    expanded = {tuple(sorted((b, t, CFG.n_heads, d)))
                for d in (CFG.nope_head_dim + CFG.rope_head_dim,
                          CFG.v_head_dim)}

    def hits(text):
        return {d for d in _shapes(text) if tuple(sorted(d)) in expanded}

    cache = transformer.abstract_cache(CFG, b, t, kv_storage=storage)
    batch = {"tokens": jax.ShapeDtypeStruct((b, 1), jnp.int32),
             "pos": jax.ShapeDtypeStruct((b,), jnp.int32)}
    decode = jax.jit(step_lib.make_decode_step(CFG, t, "bf16", storage))
    assert not hits(decode.lower(params, cache, batch).as_text())

    prefill = jax.jit(step_lib.make_prefill_step(CFG))
    prompts = {"tokens": jax.ShapeDtypeStruct((b, t), jnp.int32)}
    assert hits(prefill.lower(params, prompts).as_text())
