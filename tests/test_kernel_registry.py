"""Tunable-kernel registry (repro.kernels.api / tuned / tune): every
registered op bit-matches its reference across the tunable-axis grid
(exact axes bit-for-bit, the rest within the op's fp tolerance), tuned
points round-trip through the persisted cache (including the
stale-device-kind miss), oversized cached points clamp to shorter
operands instead of tripping grid asserts, and a second sweep of a tuned
cell is served from cache with ZERO re-evaluations."""

import itertools
import json

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import api, tune, tuned
from repro.kernels.flash_attn.ops import flash_attention
from repro.kernels.rmsnorm.ops import rmsnorm


@pytest.fixture()
def tuned_dir(tmp_path, monkeypatch):
    """Point the tuned-point cache at a throwaway dir for this test."""
    monkeypatch.setenv("REPRO_TUNED_DIR", str(tmp_path))
    tuned.invalidate_memo()
    yield tmp_path
    tuned.invalidate_memo()


class TestFitBlock:
    @pytest.mark.parametrize("value,extent,expect", [
        (512, 256, 256),      # clamp to extent
        (512, 512, 512),      # exact fit
        (128, 512, 128),      # already a divisor
        (512, 100, 100),      # clamp, divides
        (96, 256, 32),        # 256 % 96 != 0 -> gcd
        (256, 300, 4),        # gcd fallback on awkward extents
        (7, 512, 1),          # coprime -> 1, never asserts
        (512, 0, 512),        # degenerate extent: leave value alone
    ])
    def test_table(self, value, extent, expect):
        got = api.fit_block(value, extent)
        assert got == expect
        if extent > 0:
            assert extent % got == 0      # the invariant every grid needs


class TestRegistry:
    def test_builtin_ops_registered(self):
        names = set(api.ops())
        assert {"compact_pack", "flash_attn", "decode_attn",
                "paged_attn", "rmsnorm", "expert_a2a", "expert_gmm"} <= names

    def test_register_rejects_default_outside_candidates(self):
        bad = api.TunableOp(
            name="bad", axes={"b": (1, 2)}, default={"b": 3},
            run=lambda p: None, ref=lambda: None,
            clamp=lambda p: p, shape_key=lambda: "x",
            example=lambda q: ((), {}))
        with pytest.raises(ValueError):
            api.register(bad)

    @pytest.mark.parametrize("backend,interpret", [
        ("tpu", False), ("cpu", True), ("gpu", None)])
    def test_interpret_only_on_cpu(self, monkeypatch, backend, interpret):
        """No hidden fallback: Mosaic kernels compile on TPU, interpret on
        CPU, and refuse any other backend instead of interpreting."""
        import jax
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        if interpret is None:
            with pytest.raises(RuntimeError, match="gpu"):
                api.use_interpret()
        else:
            assert api.use_interpret() is interpret

    def test_dispatch_records_point_and_source(self, tuned_dir):
        """Every kernel dispatch records the point it ran and where the
        point came from, so a run can show it never read a tuned cache."""
        op = api.get_op("compact_pack")
        args, kwargs = op.example(True)
        api.call("compact_pack", *args, **kwargs)       # not recorded
        with api.record_dispatches() as log:
            api.call("compact_pack", *args, **kwargs)
            api.call("compact_pack", *args, point={"block_chunks": 2},
                     **kwargs)
            tuned.store("compact_pack", op.shape_key(*args, **kwargs),
                        {"block_chunks": 4}, objective_us=1.0,
                        evaluations=1)
            api.call("compact_pack", *args, **kwargs)
            api.call("compact_pack", *args, use_ref=True, **kwargs)
        got = [(d.op, d.point["block_chunks"], d.source) for d in log]
        assert got == [("compact_pack", 1, "default"),
                       ("compact_pack", 2, "explicit"),
                       ("compact_pack", 4, "tuned")]

    def test_explicit_point_ignores_unknown_axes(self):
        op = api.get_op("rmsnorm")
        x = jnp.ones((64, 128), jnp.float32)
        sc = jnp.ones((128,), jnp.float32)
        out = api.call("rmsnorm", x, sc,
                       point={"block_rows": 64, "bogus_axis": 999})
        ref = op.ref(x, sc)
        assert np.allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


class TestGridBitMatch:
    """The property the registry exists to defend: every candidate point
    is a correct implementation — the tuner can only trade speed."""

    @pytest.mark.parametrize("name", ["compact_pack", "flash_attn",
                                      "decode_attn", "paged_attn",
                                      "rmsnorm", "expert_a2a", "expert_gmm"])
    def test_every_grid_point_matches_ref(self, name):
        op = api.get_op(name)
        args, kwargs = op.example(True)
        axes = api.clamped_axes(op, *args, **kwargs)
        ref = np.asarray(op.ref(*args, **kwargs), np.float32)
        outs = {}
        for combo in itertools.product(*axes.values()):
            point = dict(zip(axes, combo))
            out = np.asarray(op.run(op.clamp(dict(point), *args, **kwargs),
                                    *args, **kwargs), np.float32)
            outs[combo] = out
            if op.tol == 0.0:
                assert np.array_equal(out, ref), (name, point)
            else:
                assert np.max(np.abs(out - ref)) <= op.tol, (name, point)
        # exact axes: varying ONLY that axis never changes a bit
        names = list(axes)
        for axis in op.exact_axes:
            i = names.index(axis)
            groups = {}
            for combo, out in outs.items():
                groups.setdefault(combo[:i] + combo[i + 1:], []).append(out)
            for rest, group in groups.items():
                for other in group[1:]:
                    assert np.array_equal(group[0], other), (name, axis, rest)


class TestTunedCache:
    def test_round_trip(self, tuned_dir):
        tuned.store("flash_attn", "s256", {"block_q": 128, "block_k": 256},
                    objective_us=123.4, evaluations=7)
        assert tuned.lookup("flash_attn", "s256") \
            == {"block_q": 128, "block_k": 256}
        rec = tuned.entry("flash_attn", "s256")
        assert rec["objective_us"] == pytest.approx(123.4)
        assert rec["evaluations"] == 7
        assert tuned.lookup("flash_attn", "s999") is None

    def test_stale_device_kind_is_clean_miss(self, tuned_dir):
        """A cache written on another device kind must not serve its
        blocks here — lookup misses, dispatch falls back to the default;
        the raw entry stays readable for reporting."""
        tuned.store("rmsnorm", "r512", {"block_rows": 64},
                    objective_us=1.0, evaluations=4)
        path = tuned.cache_path()
        payload = json.loads(path.read_text())
        payload["points"]["rmsnorm|r512"]["device_kind"] = "tpu-v9999"
        path.write_text(json.dumps(payload))
        tuned.invalidate_memo()
        assert tuned.lookup("rmsnorm", "r512") is None
        assert tuned.entry("rmsnorm", "r512")["point"] == {"block_rows": 64}
        op = api.get_op("rmsnorm")
        x = jnp.ones((512, 128), jnp.float32)
        sc = jnp.ones((128,), jnp.float32)
        assert api.resolve_point(op, x, sc) == api.default_point(op)

    def test_corrupt_cache_file_is_miss(self, tuned_dir):
        tuned.cache_path().parent.mkdir(parents=True, exist_ok=True)
        tuned.cache_path().write_text("{not json")
        tuned.invalidate_memo()
        assert tuned.lookup("flash_attn", "anything") is None

    def test_oversized_cached_point_clamps_on_serve(self, tuned_dir):
        """A tuned point with blocks larger than the operand (schema
        drift, hand-edited cache) is clamped at call time, not trusted."""
        x = jnp.linspace(-2, 2, 300 * 128, dtype=jnp.float32
                         ).reshape(300, 128)
        sc = jnp.ones((128,), jnp.float32)
        op = api.get_op("rmsnorm")
        skey = op.shape_key(x, sc)
        tuned.store("rmsnorm", skey, {"block_rows": 1024},
                    objective_us=1.0, evaluations=1)
        out = rmsnorm(x, sc)                 # 300 rows, 1024 clamps to 300
        ref = rmsnorm(x, sc, use_ref=True)
        assert np.array_equal(np.asarray(out), np.asarray(ref))

    def test_explicit_oversized_blocks_clamp(self):
        """The pre-registry wrappers asserted on non-dividing blocks;
        every wrapper now fits them to the operand extent."""
        import jax
        key = jax.random.PRNGKey(0)
        q = jax.random.normal(key, (1, 2, 128, 64), jnp.float32)
        k = jax.random.normal(key, (1, 1, 128, 64), jnp.float32)
        v = jax.random.normal(key, (1, 1, 128, 64), jnp.float32)
        out = flash_attention(q, k, v, block_q=1024, block_k=1024)
        ref = flash_attention(q, k, v, use_ref=True)
        assert np.max(np.abs(np.asarray(out) - np.asarray(ref))) < 5e-2


class TestTuneHarness:
    def test_sweep_finds_nondefault_point_then_serves_from_cache(
            self, tuned_dir):
        """The tentpole acceptance path: the exhaustive sweep finds a
        non-default best point for compact_pack on this host (coarser DMA
        blocks beat the chunk-at-a-time default), persists it, and the
        second run is a cache hit with ZERO re-evaluations."""
        first = tune.tune_op("compact_pack", quick=True, iters=1)
        assert not first.cache_hit
        assert first.evaluations >= len(
            api.clamped_axes(api.get_op("compact_pack"),
                             *api.get_op("compact_pack").example(True)[0])
            ["block_chunks"])
        assert first.point["block_chunks"] > 1      # non-default winner
        second = tune.tune_op("compact_pack", quick=True, iters=1)
        assert second.cache_hit
        assert second.evaluations == 0
        assert second.point == first.point

    def test_tuned_point_serves_deterministically(self, tuned_dir):
        """Once a point is cached, api.call resolves it on every call and
        the op output is bit-stable across calls."""
        tune.tune_op("compact_pack", quick=True, iters=1)
        op = api.get_op("compact_pack")
        args, kwargs = op.example(True)
        assert api.resolve_point(op, *args, **kwargs)["block_chunks"] > 1
        a = np.asarray(api.call("compact_pack", *args, **kwargs))
        b = np.asarray(api.call("compact_pack", *args, **kwargs))
        assert np.array_equal(a, b)

    def test_expert_a2a_sweep_then_cache_hit(self, tuned_dir):
        """The expert all-to-all inherits the sweep harness like every
        registered op: first sweep evaluates the (clamped, deduped) block
        grid and persists, the second is a pure cache hit."""
        first = tune.tune_op("expert_a2a", quick=True, iters=1)
        assert not first.cache_hit
        op = api.get_op("expert_a2a")
        args, kwargs = op.example(True)
        assert first.evaluations >= len(
            api.clamped_axes(op, *args, **kwargs)["block"])
        second = tune.tune_op("expert_a2a", quick=True, iters=1)
        assert second.cache_hit
        assert second.evaluations == 0
        assert second.point == first.point
        assert api.resolve_point(op, *args, **kwargs) == first.point


class TestFusedFilterPack:
    """The fused filter+pack kernel vs the filter-then-pack reference:
    bit-identical across plan shapes, keep fractions, and DMA
    granularities (the whole point of exact_axes for compact_pack) — and
    across the SMEM-bounded grid segments both compaction kernels run a
    long plan in (``segment`` steps per ``pallas_call``; None = the
    public path at the SMEM-derived segment length)."""

    @staticmethod
    def _check(src, cm, keep, segment):
        from repro.kernels.compact_pack import compact_chunks
        from repro.kernels.compact_pack.compact_pack import (
            CHUNK_COLS, CHUNK_ROWS, CHUNK_TOKENS, compact_chunks_kernel,
            compact_filter_kernel)
        from repro.kernels.compact_pack.ops import plan_filter
        from repro.kernels.compact_pack.ref import compact_chunks_ref
        ref = np.asarray(compact_chunks(src, cm, use_ref=True,
                                        keep_mask=keep))
        if segment is None:
            fused = np.asarray(compact_chunks(src, cm, keep_mask=keep))
        else:
            src3 = src.reshape(-1, CHUNK_ROWS, CHUNK_COLS)
            sel, dest, done, out_idx, n_out = plan_filter(cm, keep)
            fused = np.asarray(compact_filter_kernel(
                src3, *map(jnp.asarray, (sel, dest, done, out_idx)), n_out,
                interpret=True, segment=segment)).reshape(-1)
            gather = compact_chunks_kernel(src3, jnp.asarray(cm),
                                           interpret=True, segment=segment)
            assert np.array_equal(np.asarray(gather), np.asarray(
                compact_chunks_ref(src3, jnp.asarray(cm))))
        assert np.array_equal(fused, ref)
        assert fused.shape[0] == \
            -(-int(keep.sum()) // CHUNK_ROWS) * CHUNK_TOKENS

    @staticmethod
    def _src(n_chunks, seed):
        from repro.kernels.compact_pack.compact_pack import CHUNK_TOKENS
        rng = np.random.RandomState(seed)
        return jnp.asarray(rng.randint(0, 1 << 30, n_chunks * CHUNK_TOKENS,
                                       np.int64).astype(np.int32))

    @pytest.mark.parametrize("counts,order", [
        ([4, 4, 4, 4], [3, 1, 2, 0]),
        ([2, 6, 8], None),
        ([3, 1, 2], [2, 0, 1]),
    ])
    @pytest.mark.parametrize("frac", [0.0, 0.3, 1.0])
    @pytest.mark.parametrize("segment", [None, 5])
    def test_fused_matches_reference(self, counts, order, frac, segment):
        from repro.kernels.compact_pack import plan_compaction
        from repro.kernels.compact_pack.compact_pack import CHUNK_ROWS
        seed = hash((tuple(counts), frac)) % (1 << 31)
        src = self._src(sum(counts), seed)
        cm = plan_compaction(counts, fragment_order=order)
        rng = np.random.RandomState(seed + 1)
        keep = rng.rand(len(cm) * CHUNK_ROWS) >= frac
        self._check(src, cm, keep, segment)

    @pytest.mark.parametrize("kept,segment", [
        # 18 rows in 6 steps: the carry crosses both boundaries (1 and 2
        # rows), and the final segment is the all-dropped flush step
        # alone, emitting the 2-row carry
        ([3, 3, 3, 3, 3, 3], 3),
        # fully-dropped chunks never enter a segment; the kept chunks
        # straddle the boundaries with a partial carry
        ([3, 0, 0, 8, 5, 0, 0, 3], 2),
        # chunk-aligned keeps: no carry and no flush step at any boundary
        ([8, 8, 8], 1),
    ])
    def test_segment_edges(self, kept, segment):
        from repro.kernels.compact_pack import plan_compaction
        from repro.kernels.compact_pack.compact_pack import CHUNK_ROWS
        cm = plan_compaction([len(kept)])
        keep = (np.arange(CHUNK_ROWS)[None, :]
                < np.asarray(kept)[:, None]).reshape(-1)
        self._check(self._src(len(kept), len(kept)), cm, keep, segment)
