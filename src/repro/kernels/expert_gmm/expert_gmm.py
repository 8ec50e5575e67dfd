"""Pallas TPU kernel: the grouped matmul of a layer's held experts.

Rows arrive grouped by expert, each group padded to a whole number of
``block_rows`` tiles, so every tile belongs to one expert. One grid step
``(tile, chunk)`` takes the tile's rows through a ``block_ff`` slice of
that expert's SwiGLU: ``silu(x @ gate) * (x @ up)`` in float32, cast to
the weights' dtype, then ``@ down`` accumulated in a float32 tile over
the chunks; the last chunk writes the tile. The intermediate never
leaves VMEM, and each expert's weights are read once per tile of its
rows.

The weights are the whole stack of a model's held experts, ``(G, d, f)``
with ``G`` = layers x experts: the tile's group index (scalar-prefetched,
layer offset included) picks the block, so no layer's weights are ever
sliced out into a copy. The grid's first extent is the number of tiles
that hold rows, a traced value: tiles past it are never visited.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.spans import KERNEL_EXPERT_GMM

VMEM_HEADROOM = 4 * 1024 * 1024


def _kernel(tile_group_ref, x_ref, wg_ref, wu_ref, wd_ref, o_ref, acc_ref):
    del tile_group_ref                       # read by the index maps
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    g = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
    u = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
    h = (jax.nn.silu(g) * u).astype(wd_ref.dtype)
    acc_ref[...] += jnp.dot(h, wd_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(c == pl.num_programs(1) - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def expert_gmm_kernel(x, w_gate, w_up, w_down, tile_group, n_tiles, *,
                      block_rows: int, block_ff: int, interpret: bool):
    """x: (M, d), M a multiple of ``block_rows``; w_gate, w_up: (G, d, f);
    w_down: (G, f, d); tile_group: (M // block_rows,) int32, the group of
    each tile; n_tiles: () int32, how many leading tiles hold rows.
    Returns (M, d) in x's dtype; rows of tiles past ``n_tiles`` are not
    written."""
    m, d = x.shape
    f = w_gate.shape[-1]
    tm, tf = block_rows, block_ff
    assert m % tm == 0 and f % tf == 0, (m, tm, f, tf)
    item = jnp.dtype(w_gate.dtype).itemsize
    vmem = (2 * (3 * d * tf * item + 2 * tm * d * x.dtype.itemsize)
            + tm * d * 4 + VMEM_HEADROOM)
    return pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((m, d), x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(jnp.maximum(n_tiles, 1), f // tf),
            in_specs=[
                pl.BlockSpec((tm, d), lambda t, c, tg: (t, 0)),
                pl.BlockSpec((None, d, tf), lambda t, c, tg: (tg[t], 0, c)),
                pl.BlockSpec((None, d, tf), lambda t, c, tg: (tg[t], 0, c)),
                pl.BlockSpec((None, tf, d), lambda t, c, tg: (tg[t], c, 0)),
            ],
            out_specs=pl.BlockSpec((tm, d), lambda t, c, tg: (t, 0)),
            scratch_shapes=[pltpu.VMEM((tm, d), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=int(vmem)),
        interpret=interpret,
        name=KERNEL_EXPERT_GMM,
    )(tile_group, x, w_gate, w_up, w_down)
