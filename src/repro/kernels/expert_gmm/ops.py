"""The held experts' grouped matmul, registered on the tunable-op
registry under its kernel's name (``spans.KERNEL_EXPERT_GMM``).

``expert_gmm(x, groups, w_gate, w_up, w_down, layer)`` computes, for
every (token, choice) pair whose expert this chip holds, that expert's
SwiGLU of the token, and zero for the others: work in proportion to the
held pairs, with no capacity and no dropped pair. The wrapper lays the
pairs out for the kernel (``expert_gmm.py``): grouped by expert, each
group padded to whole ``block_rows`` tiles, then the tile table and the
count of tiles that hold rows; the kernel's output rows are gathered
back per pair.

Axes: ``block_rows`` (rows per tile: padding against how often an
expert's weights are read again) and ``block_ff`` (the slice of the
expert width a grid step takes: DMA size against VMEM). Neither regroups
a sum over tokens, and ``block_rows`` keeps bits; ``block_ff`` splits the
down projection's float32 accumulation.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import api
from repro.kernels.expert_gmm.expert_gmm import expert_gmm_kernel
from repro.kernels.expert_gmm.ref import expert_gmm_ref
from repro.spans import KERNEL_EXPERT_GMM

BLOCK_ROWS_CANDIDATES = (16, 32, 64, 128)
BLOCK_FF_CANDIDATES = (128, 256, 512, 1408)


def layout(groups, n_experts: int, block_rows: int):
    """Where each pair's row goes: ``(row (P,), tile_group (T,),
    n_tiles)``. Held groups are laid out in expert order, each padded
    to whole tiles; a pair that is not held gets row ``M`` (outside)."""
    tm = block_rows
    flat = groups.reshape(-1)
    p = flat.shape[0]
    onehot = (flat[:, None] == jnp.arange(n_experts)).astype(jnp.int32)
    counts = onehot.sum(0)
    tiles = (counts + tm - 1) // tm
    ends = jnp.cumsum(tiles)
    rank = jnp.sum((jnp.cumsum(onehot, 0) - onehot) * onehot, 1)
    n_max = -(-p // tm) + n_experts          # sum of ceil(c_e / tm)
    start = jnp.take(ends - tiles, jnp.clip(flat, 0, n_experts - 1))
    row = jnp.where(flat >= 0, start * tm + rank, n_max * tm)
    tile_group = jnp.minimum(
        jnp.searchsorted(ends, jnp.arange(n_max), side="right"),
        n_experts - 1).astype(jnp.int32)
    return row.astype(jnp.int32), tile_group, ends[-1]


@partial(jax.jit, static_argnames=("block_rows", "block_ff", "interpret"))
def _run_jit(x, groups, w_gate, w_up, w_down, layer, *, block_rows,
             block_ff, interpret):
    t, k = groups.shape
    l, e, d, f = w_gate.shape
    row, tile_group, n_tiles = layout(groups, e, block_rows)
    m = tile_group.shape[0] * block_rows
    xs = jnp.zeros((m, d), x.dtype).at[row].set(
        jnp.repeat(x, k, axis=0), mode="drop")
    ys = expert_gmm_kernel(
        xs, w_gate.reshape(l * e, d, f), w_up.reshape(l * e, d, f),
        w_down.reshape(l * e, f, d), tile_group + layer * e, n_tiles,
        block_rows=block_rows, block_ff=block_ff, interpret=interpret)
    held = (groups.reshape(-1) >= 0)[:, None]
    y = jnp.where(held, jnp.take(ys, jnp.minimum(row, m - 1), axis=0), 0)
    return y.reshape(t, k, d)


def _run(point, x, groups, w_gate, w_up, w_down, layer):
    return _run_jit(x, groups, w_gate, w_up, w_down, layer,
                    block_rows=point["block_rows"],
                    block_ff=point["block_ff"],
                    interpret=api.use_interpret())


def _clamp(point, x, groups, w_gate, *args, **kw):
    return {"block_rows": point["block_rows"],
            "block_ff": api.fit_block(point["block_ff"], w_gate.shape[-1])}


def _shape_key(x, groups, w_gate, *args, **kw):
    l, e, d, f = w_gate.shape
    return f"t{groups.shape[0]}k{groups.shape[1]}l{l}e{e}d{d}f{f}:" \
           f"{w_gate.dtype.name}"


def _example(quick: bool):
    t, k, l, e, d, f = (24, 2, 2, 4, 128, 256) if quick else \
        (128, 6, 4, 8, 2048, 1408)
    ks = jax.random.split(jax.random.PRNGKey(0), 5)

    def w(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                / fan_in ** 0.5).astype(jnp.bfloat16)
    x = w(ks[0], (t, d), 1)
    groups = jax.random.randint(ks[1], (t, k), -1, e).astype(jnp.int32)
    return (x, groups, w(ks[2], (l, e, d, f), d), w(ks[3], (l, e, d, f), d),
            w(ks[4], (l, e, f, d), f), jnp.int32(1)), {}


api.register(api.TunableOp(
    name=KERNEL_EXPERT_GMM,
    axes={"block_rows": BLOCK_ROWS_CANDIDATES,
          "block_ff": BLOCK_FF_CANDIDATES},
    default={"block_rows": 32, "block_ff": 128},
    run=_run,
    ref=expert_gmm_ref,
    clamp=_clamp,
    shape_key=_shape_key,
    example=_example,
    exact_axes=frozenset({"block_rows"}),
    tol=2e-2,
))


def expert_gmm(x, groups, w_gate, w_up, w_down, layer, *, point=None,
               use_ref=False):
    """Per (token, choice) pair, the held expert's SwiGLU of the token:
    x (T, d), groups (T, k) int32 (held expert index, or -1), stacked
    weights (L, E, d, f), (L, E, d, f), (L, E, f, d), layer () int32 ->
    (T, k, d)."""
    return api.call(KERNEL_EXPERT_GMM, x, groups, w_gate, w_up, w_down,
                    jnp.asarray(layer, jnp.int32), point=point,
                    use_ref=use_ref)
