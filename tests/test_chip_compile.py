"""Compile-only checks for a described TPU v5e: no chip needed.

The compaction kernels run interpreted in every other test, which cannot
see what Mosaic refuses on the chip — scalar-prefetch tables that
overflow the 1 MiB of SMEM, for one. Here they are compiled for a
described v5e at deployment size: a 512 MiB rewrite (131 072 chunks,
Iceberg's default target file size) and a 2 GiB one (524 288 chunks).
Each must lower to the Mosaic custom call, one per SMEM-bounded segment.

The topology is described inside a fixture, never at import, so every
pytest-xdist worker collects the same tests and only the worker that
runs this file loads the TPU compiler.
"""

import math

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.compact_pack.compact_pack import (
    CHUNK_COLS, CHUNK_ROWS, FILTER_SEGMENT, GATHER_SEGMENT,
    compact_chunks_kernel, compact_filter_kernel)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means no TPU
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent
        # cache but cannot be read back without one: keep it out
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()


def _i32(sharding, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)


@pytest.mark.parametrize("n_chunks", [131072, 524288])
def test_gather_compiles_at_deployment_size(one_chip, n_chunks):
    hlo = jax.jit(compact_chunks_kernel).lower(
        _i32(one_chip, n_chunks, CHUNK_ROWS, CHUNK_COLS),
        _i32(one_chip, n_chunks)).compile().as_text()
    assert hlo.count("tpu_custom_call") == math.ceil(n_chunks
                                                     / GATHER_SEGMENT)


def test_fused_filter_compiles_at_deployment_size(one_chip):
    n = 131072                           # touched chunks of a 512 MiB file
    hlo = jax.jit(compact_filter_kernel, static_argnames=("n_out",)).lower(
        _i32(one_chip, n, CHUNK_ROWS, CHUNK_COLS), _i32(one_chip, n),
        _i32(one_chip, n * CHUNK_ROWS), _i32(one_chip, n),
        _i32(one_chip, n), n_out=n).compile().as_text()
    assert hlo.count("tpu_custom_call") == math.ceil(n / FILTER_SEGMENT)


@pytest.mark.parametrize("point", [
    {"block_rows": 32, "block_ff": 128},        # the default
    {"block_rows": 128, "block_ff": 1408},      # the largest blocks
])
def test_expert_gmm_compiles_at_deployment_size(one_chip, point):
    """The held experts' grouped matmul at Moonlight-16B-A3B's decode
    size: 128 tokens x top-6 over the stacked weights of 26 layers x 8
    held experts (2048 x 1408), one Mosaic call named after the kernel,
    with no copy of a layer's weights."""
    from repro.kernels.expert_gmm import ops
    from repro.spans import KERNEL_EXPERT_GMM

    def sds(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    bf16 = jnp.bfloat16
    hlo = jax.jit(lambda *a: ops._run_jit(
        *a, interpret=False, **point)).lower(
        sds(bf16, 128, 2048), sds(jnp.int32, 128, 6),
        sds(bf16, 26, 8, 2048, 1408), sds(bf16, 26, 8, 2048, 1408),
        sds(bf16, 26, 8, 1408, 2048), sds(jnp.int32)).compile().as_text()
    calls = [line for line in hlo.splitlines() if "tpu_custom_call" in line]
    assert len(calls) == 1
    assert calls[0].lstrip().startswith(f"%{KERNEL_EXPERT_GMM}.")
    assert "bf16[8,2048,1408]" not in hlo
