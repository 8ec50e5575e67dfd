"""Plain reference of the held experts' grouped matmul: the (token,
choice) pairs sorted by expert and put through ``jax.lax.ragged_dot``
(gate, up, SiLU, down), with no padding, tiling or stacking trick.
Differentiable, so training runs it too."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def expert_gmm_ref(x, groups, w_gate, w_up, w_down, layer):
    """x: (T, d); groups: (T, k) int32, the held expert of each pair or
    -1; w_*: (L, E, ...) stacked; layer: () int32. Returns (T, k, d) in
    x's dtype, zero for pairs whose expert is not held."""
    t, k = groups.shape
    e = w_gate.shape[1]
    flat = groups.reshape(-1)
    key = jnp.where(flat >= 0, flat, e)          # not held: sorted last
    order = jnp.argsort(key, stable=True)
    sizes = jnp.sum(flat[:, None] == jnp.arange(e), 0).astype(jnp.int32)
    xs = jnp.take(x, order // k, axis=0)
    wg, wu, wd = (jax.lax.dynamic_index_in_dim(w, layer, keepdims=False)
                  for w in (w_gate, w_up, w_down))
    f32 = jnp.float32
    g = jax.lax.ragged_dot(xs, wg, sizes, preferred_element_type=f32)
    u = jax.lax.ragged_dot(xs, wu, sizes, preferred_element_type=f32)
    h = (jax.nn.silu(g) * u).astype(wd.dtype)
    y = jax.lax.ragged_dot(h, wd, sizes, preferred_element_type=f32)
    y = jnp.where((key[order] < e)[:, None], y, 0.0).astype(x.dtype)
    out = jnp.zeros_like(y).at[order].set(y)
    return out.reshape(t, k, -1)
