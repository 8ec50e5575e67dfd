"""Serve-path tests that run on any device count: ragged continuous
batching (per-row masking — every row of a mixed-length batch must match a
solo run of its unpadded prompt), cache growth padding, and sampling
determinism. The sharded/transport claims live in
tests/test_serve_multidevice.py (8 forced devices)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.launch.serve import generate, grow_cache
from repro.models import transformer
from repro.train import step as step_lib


@pytest.fixture(scope="module")
def dense():
    cfg = smoke_config("granite-3-8b")
    return cfg, transformer.init_params(cfg, jax.random.PRNGKey(1))


def _prompts(cfg, b, s, seed=0):
    return np.random.RandomState(seed).randint(
        0, cfg.vocab, size=(b, s)).astype(np.int32)


class TestRaggedContinuousBatching:
    def test_mixed_lengths_match_solo_runs(self, dense):
        """Rows at different positions share the decode step; pad slots are
        junk from prefill and must never leak into any row's tokens."""
        cfg, params = dense
        prompts = _prompts(cfg, 3, 12, seed=3)
        lens = np.array([5, 12, 9], np.int32)
        mixed = generate(cfg, params, prompts, max_new=6, prompt_lens=lens)
        for i, n in enumerate(lens):
            solo = generate(cfg, params, prompts[i:i + 1, :n], max_new=6)
            assert (mixed[i] == solo[0]).all(), (i, mixed[i], solo[0])

    def test_pad_contents_never_observed(self, dense):
        """Same ragged batch, different junk in the pad slots => identical
        outputs (the masking claim, tested directly)."""
        cfg, params = dense
        lens = np.array([4, 9, 7], np.int32)
        a = _prompts(cfg, 3, 9, seed=5)
        b = a.copy()
        for i, n in enumerate(lens):
            b[i, n:] = (b[i, n:] + 17) % cfg.vocab   # different junk
        out_a = generate(cfg, params, a, max_new=5, prompt_lens=lens)
        out_b = generate(cfg, params, b, max_new=5, prompt_lens=lens)
        assert (out_a == out_b).all()

    def test_full_lens_equals_uniform_path(self, dense):
        """prompt_lens=[S0]*B must reproduce the scalar-position path."""
        cfg, params = dense
        prompts = _prompts(cfg, 4, 8, seed=7)
        uniform = generate(cfg, params, prompts, max_new=5)
        ragged = generate(cfg, params, prompts, max_new=5,
                          prompt_lens=np.full((4,), 8, np.int32))
        assert (uniform == ragged).all()

    @pytest.mark.parametrize("arch", ["hymba-1.5b", "xlstm-125m"])
    def test_ragged_refused_for_ring_and_recurrent_families(self, arch):
        """Ring buffers alias padded junk slots into the window and
        recurrent states scan pad tokens in — per-row masks can't undo
        either, so ragged serving must refuse loudly, not drift."""
        cfg = smoke_config(arch)
        params = transformer.init_params(cfg, jax.random.PRNGKey(2))
        with pytest.raises(NotImplementedError, match="ragged"):
            generate(cfg, params, _prompts(cfg, 2, 10), max_new=2,
                     prompt_lens=np.array([6, 10], np.int32))

    @pytest.mark.parametrize("arch", ["hymba-1.5b", "xlstm-125m"])
    def test_uniform_decode_families_still_serve(self, arch):
        """Signature changes (per-row pos plumbing) must not break the
        ring-buffer (SWA) and recurrent-state families on the scalar
        position path."""
        cfg = smoke_config(arch)
        params = transformer.init_params(cfg, jax.random.PRNGKey(2))
        out = generate(cfg, params, _prompts(cfg, 2, 10), max_new=4)
        assert out.shape == (2, 4)
        assert ((out >= 0) & (out < cfg.vocab)).all()


class TestCacheGrow:
    def test_grow_pads_end_and_casts(self, dense):
        cfg, params = dense
        b, s0, total = 2, 6, 14
        prefill = jax.jit(step_lib.make_prefill_step(cfg))
        _, cache = prefill(params, {"tokens": jnp.asarray(_prompts(cfg, b, s0))})
        target = transformer.abstract_cache(cfg, b, total)
        grown = grow_cache(cache, target)
        for leaf, tgt in zip(jax.tree.leaves(grown), jax.tree.leaves(target)):
            assert leaf.shape == tgt.shape and leaf.dtype == tgt.dtype
        # prefix slots preserved exactly, padded slots zero
        k0, kg = cache["k"], grown["k"]
        np.testing.assert_array_equal(np.asarray(kg[:, :, :s0]),
                                      np.asarray(k0.astype(kg.dtype)))
        assert not np.asarray(kg[:, :, s0:]).any()

    def test_grow_is_identity_at_target_shape(self, dense):
        cfg, _ = dense
        cache = transformer.init_cache(cfg, 2, 10)
        grown = grow_cache(cache, transformer.abstract_cache(cfg, 2, 10))
        for a, g in zip(jax.tree.leaves(cache), jax.tree.leaves(grown)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(g))


class TestSampling:
    def test_fixed_seed_is_deterministic(self, dense):
        cfg, params = dense
        prompts = _prompts(cfg, 3, 8, seed=11)
        one = generate(cfg, params, prompts, max_new=6, temperature=0.8,
                       seed=42)
        two = generate(cfg, params, prompts, max_new=6, temperature=0.8,
                       seed=42)
        assert (one == two).all()

    def test_seed_changes_samples(self, dense):
        cfg, params = dense
        prompts = _prompts(cfg, 4, 8, seed=11)
        a = generate(cfg, params, prompts, max_new=8, temperature=2.0, seed=0)
        b = generate(cfg, params, prompts, max_new=8, temperature=2.0, seed=1)
        assert (a != b).any()


class TestServeArgs:
    """--smoke was action="store_true" with default=True — impossible to
    disable, so the full-config branch was dead code. It is now --full."""

    def test_default_serves_smoke_config(self):
        from repro.launch.serve import build_parser, resolve_config
        args = build_parser().parse_args(["--arch", "granite-3-8b"])
        assert args.full is False
        cfg = resolve_config(args)
        assert cfg.name.endswith("-smoke")

    def test_full_flag_serves_published_config(self):
        from repro.configs import get_config
        from repro.launch.serve import build_parser, resolve_config
        args = build_parser().parse_args(["--arch", "granite-3-8b", "--full"])
        assert args.full is True
        cfg = resolve_config(args)
        assert cfg == get_config("granite-3-8b")
        assert not cfg.name.endswith("-smoke")

    def test_disagg_flags_parse(self):
        from repro.launch.serve import build_parser
        args = build_parser().parse_args(
            ["--disagg", "--cache-transfer", "int8", "--kv-storage", "int8"])
        assert args.disagg and args.cache_transfer == "int8" \
            and args.kv_storage == "int8"

    def test_stream_and_f8_flags_parse(self):
        from repro.launch.serve import build_parser
        args = build_parser().parse_args(
            ["--disagg", "--stream", "slots", "--slots", "3",
             "--cache-transfer", "int8", "--kv-storage", "f8"])
        assert args.stream == "slots" and args.slots == 3 \
            and args.kv_storage == "f8"
        assert build_parser().parse_args([]).stream == "batch"

    def test_setup_builds_params_in_their_mesh_placement(self):
        """setup() initialises the parameters under jit straight into the
        prefill mesh's shardings — the same values as an unplaced init,
        and generate()'s own placement of them is then a no-op."""
        from repro.dist import sharding as shd
        from repro.launch.serve import build_parser, setup
        args = build_parser().parse_args(["--arch", "paper-lm-100m"])
        cfg, params, prompts, kw = setup(args)
        want = shd.tree_shardings(transformer.abstract_params(cfg),
                                  transformer.param_axes(cfg), kw["mesh"],
                                  kw["rules"])
        unplaced = transformer.init_params(cfg, jax.random.PRNGKey(0))
        for p, s, e in zip(jax.tree.leaves(params), jax.tree.leaves(want),
                           jax.tree.leaves(unplaced)):
            assert p.sharding == s
            np.testing.assert_array_equal(np.asarray(p), np.asarray(e))
            assert jax.device_put(p, s) is p
        assert prompts.shape == (args.batch, args.prompt_len)
        out = generate(cfg, params, prompts, **{**kw, "max_new": 3})
        assert out.shape == (args.batch, 3)
        assert generate.last_stats["logits"] is None

    @pytest.mark.parametrize("stream", ["batch", "slots"])
    def test_keep_logits_rows_chose_the_tokens(self, stream):
        """keep_logits leaves, per request and generated token, the logit
        row it was chosen from: greedy tokens are their argmax, and the
        slot stream keeps the same rows as the whole-batch path."""
        from repro.launch.serve import _generate_slots, build_parser, setup
        args = build_parser().parse_args(["--arch", "paper-lm-100m",
                                          "--max-new", "4"])
        cfg, params, prompts, kw = setup(args)
        ref = generate(cfg, params, prompts, **kw, keep_logits=True)
        rows_ref = generate.last_stats["logits"]
        out = generate(cfg, params, prompts,
                       **{**kw, "stream": stream}, keep_logits=True)
        rows = (_generate_slots if stream == "slots"
                else generate).last_stats["logits"]
        assert rows.shape == (args.batch, 4, cfg.vocab)
        assert rows.dtype == np.float32 and np.isfinite(rows).all()
        np.testing.assert_array_equal(rows.argmax(-1), out)
        np.testing.assert_array_equal(out, ref)
        gap = np.abs(rows - rows_ref).max(-1) / rows_ref.std(-1)
        assert gap.max() < 0.05, gap.max()

    def test_compile_cache_dir(self, monkeypatch, tmp_path):
        """The env var wins; unset, the cache is a fixed path in the
        checkout, never a temp or per-run directory."""
        import pathlib

        from repro.launch import compile_cache
        monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
        assert compile_cache.compile_cache_dir() == tmp_path
        monkeypatch.delenv(compile_cache.ENV_VAR)
        root = pathlib.Path(__file__).resolve().parents[1]
        assert compile_cache.compile_cache_dir() == root / ".jax_cache"

    def test_disaggregated_serving_runs_without_the_compile_cache(
            self, monkeypatch, tmp_path):
        """A disaggregated server turns the persistent cache off for its
        process, env var or not; a colocated one keeps it."""
        from repro.launch import compile_cache
        monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
        was = jax.config.jax_enable_compilation_cache
        try:
            assert compile_cache.enable_compile_cache() == tmp_path
            assert jax.config.jax_enable_compilation_cache
            assert compile_cache.enable_compile_cache(
                disaggregated=True) is None
            assert not jax.config.jax_enable_compilation_cache
        finally:
            jax.config.update("jax_enable_compilation_cache", was)


class TestKVStorageInt8:
    """int8-resident decode cache, single-device (the sharded/transfer
    claims live in tests/test_serve_disagg.py)."""

    @pytest.mark.parametrize("arch", ["paper-lm-100m", "minicpm3-4b"])
    def test_int8_storage_logits_match_bf16(self, arch):
        cfg = smoke_config(arch)
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        b, s0, total = 2, 8, 16
        prompts = _prompts(cfg, b, s0, seed=13)
        prefill = jax.jit(step_lib.make_prefill_step(cfg))
        logits0, cache = prefill(params, {"tokens": jnp.asarray(prompts)})
        cache = grow_cache(cache, transformer.abstract_cache(cfg, b, total))
        tok = jnp.argmax(logits0, -1).astype(jnp.int32)[:, None]
        batch = {"tokens": tok, "pos": jnp.asarray(s0, jnp.int32)}
        out = {}
        for storage in ("bf16", "int8"):
            c = cache
            if storage == "int8":
                c = transformer.quantize_cache_int8(cache)
            fn = jax.jit(step_lib.make_decode_step(cfg, total, "bf16",
                                                   storage))
            lg, new_c = fn(params, c, batch)
            # the step emits the same storage layout it consumed
            assert jax.tree.structure(new_c) == jax.tree.structure(c)
            out[storage] = np.asarray(lg, np.float32)
        scale = max(np.abs(out["bf16"]).max(), 1.0)
        assert np.abs(out["bf16"] - out["int8"]).max() / scale < 0.05

    def test_int8_storage_generate_tracks_bf16_tokens(self, dense):
        cfg, params = dense
        prompts = _prompts(cfg, 3, 10, seed=17)
        base = generate(cfg, params, prompts, max_new=8)
        quant = generate(cfg, params, prompts, max_new=8, kv_storage="int8")
        rows_equal = (base == quant).all(axis=1)
        assert rows_equal.mean() >= 0.5, (base, quant)

    def test_int8_storage_cache_layout(self):
        cfg = smoke_config("paper-lm-100m")
        struct = transformer.cache_struct(cfg, 2, 16, kv_storage="int8")
        assert "k_scale" in struct and "v_scale" in struct
        abs_c = transformer.abstract_cache(cfg, 2, 16, kv_storage="int8")
        assert abs_c["k"].dtype == jnp.int8
        assert abs_c["k_scale"].dtype == jnp.float32
        assert abs_c["k_scale"].shape[:-1] == abs_c["k"].shape[:-1]

    @pytest.mark.parametrize("arch", ["hymba-1.5b", "xlstm-125m"])
    @pytest.mark.parametrize("storage", ["int8", "f8"])
    def test_recurrent_families_refuse_quantized_storage(self, arch,
                                                         storage):
        cfg = smoke_config(arch)
        with pytest.raises(NotImplementedError, match="kv_storage"):
            step_lib.make_decode_step(cfg, 16, "bf16", storage)


class TestKVStorageF8:
    """f8 (e4m3) resident decode cache: scale-free cast, same shapes as
    bf16 at half the bytes. The sharded/report claims live in
    tests/test_serve_disagg.py."""

    @pytest.mark.parametrize("arch", ["paper-lm-100m", "minicpm3-4b"])
    def test_f8_storage_logits_match_bf16(self, arch):
        cfg = smoke_config(arch)
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        b, s0, total = 2, 8, 16
        prompts = _prompts(cfg, b, s0, seed=13)
        prefill = jax.jit(step_lib.make_prefill_step(cfg))
        logits0, cache = prefill(params, {"tokens": jnp.asarray(prompts)})
        cache = grow_cache(cache, transformer.abstract_cache(cfg, b, total))
        tok = jnp.argmax(logits0, -1).astype(jnp.int32)[:, None]
        batch = {"tokens": tok, "pos": jnp.asarray(s0, jnp.int32)}
        out = {}
        for storage in ("bf16", "f8"):
            c = transformer.quantize_cache(cache, storage)
            fn = jax.jit(step_lib.make_decode_step(cfg, total, "bf16",
                                                   storage))
            lg, new_c = fn(params, c, batch)
            # the step emits the same storage layout it consumed
            assert jax.tree.structure(new_c) == jax.tree.structure(c)
            if storage == "f8":
                from repro.dist.collectives import F8_DTYPE
                quant_keys = [k for k in new_c
                              if k in transformer.QUANTIZABLE_CACHE_KEYS]
                assert quant_keys
                for k in quant_keys:
                    assert new_c[k].dtype == F8_DTYPE
            out[storage] = np.asarray(lg, np.float32)
        scale = max(np.abs(out["bf16"]).max(), 1.0)
        assert np.abs(out["bf16"] - out["f8"]).max() / scale < 0.08

    def test_f8_storage_generate_tracks_bf16_tokens(self, dense):
        cfg, params = dense
        prompts = _prompts(cfg, 3, 10, seed=17)
        base = generate(cfg, params, prompts, max_new=8)
        quant = generate(cfg, params, prompts, max_new=8, kv_storage="f8")
        rows_equal = (base == quant).all(axis=1)
        assert rows_equal.mean() >= 0.5, (base, quant)

    def test_f8_storage_cache_layout_scale_free_half_bytes(self):
        from repro.dist.collectives import F8_DTYPE
        cfg = smoke_config("paper-lm-100m")
        bf = transformer.abstract_cache(cfg, 2, 16)
        f8 = transformer.abstract_cache(cfg, 2, 16, kv_storage="f8")
        assert set(f8) == set(bf)                  # no _scale companions
        assert f8["k"].dtype == F8_DTYPE and f8["k"].shape == bf["k"].shape

        def nbytes(tree):
            return sum(np.prod(l.shape) * l.dtype.itemsize
                       for l in jax.tree.leaves(tree))
        assert nbytes(f8) == nbytes(bf) / 2


class TestSlotStreaming:
    """Continuous slot-level streaming, single device (the disagg mesh
    claims live in tests/test_serve_disagg.py): admission into a running
    decode batch must reproduce the whole-batch path token-for-token,
    including when a small slot table forces slots to be freed and
    reused across admissions."""

    def test_slot_stream_matches_batch_ragged(self, dense):
        cfg, params = dense
        prompts = _prompts(cfg, 3, 12, seed=3)
        lens = np.array([5, 12, 9], np.int32)
        batch = generate(cfg, params, prompts, max_new=6, prompt_lens=lens)
        slot = generate(cfg, params, prompts, max_new=6, prompt_lens=lens,
                        stream="slots")
        assert (batch == slot).all(), (batch, slot)

    def test_slot_reuse_no_cross_request_bleed(self, dense):
        """slots=1 serializes every request through ONE slot row — each
        admission must fully overwrite the previous occupant. The plain
        slot stream never preempts: every request is admitted once."""
        from repro.launch.serve import _generate_slots
        cfg, params = dense
        prompts = _prompts(cfg, 4, 10, seed=23)
        lens = np.array([4, 10, 7, 9], np.int32)
        batch = generate(cfg, params, prompts, max_new=5, prompt_lens=lens)
        for n_slots in (1, 2):
            slot = generate(cfg, params, prompts, max_new=5,
                            prompt_lens=lens, stream="slots", slots=n_slots)
            assert (batch == slot).all(), (n_slots, batch, slot)
            st = _generate_slots.last_stats
            assert st["admissions"] == len(prompts)
            assert st["evictions"] == st["requeues"] == 0

    def test_slot_stream_uniform_and_quantized_pipeline(self, dense):
        cfg, params = dense
        prompts = _prompts(cfg, 3, 8, seed=29)
        batch = generate(cfg, params, prompts, max_new=5)
        slot = generate(cfg, params, prompts, max_new=5, stream="slots")
        assert (batch == slot).all()
        # the fully quantized continuous pipeline still produces sane,
        # mostly-agreeing tokens (lossy: s8 wire + f8-resident cache)
        q = generate(cfg, params, prompts, max_new=5, stream="slots",
                     cache_transfer="int8", kv_storage="f8")
        assert q.shape == batch.shape
        assert ((q >= 0) & (q < cfg.vocab)).all()
        assert (batch == q).all(axis=1).mean() >= 0.5

    def test_single_token_requests_all_served(self, dense):
        """max_new=1: each request IS its prefill token, so every slot
        frees at admission — the loop must keep refilling the table
        instead of breaking with requests unserved."""
        cfg, params = dense
        prompts = _prompts(cfg, 5, 8, seed=37)
        batch = generate(cfg, params, prompts, max_new=1)
        slot = generate(cfg, params, prompts, max_new=1, stream="slots",
                        slots=2)
        assert slot.shape == (5, 1)
        assert (batch == slot).all(), (batch, slot)

    def test_slot_stream_sampling_is_deterministic(self, dense):
        cfg, params = dense
        prompts = _prompts(cfg, 3, 8, seed=31)
        one = generate(cfg, params, prompts, max_new=5, temperature=0.8,
                       seed=42, stream="slots", slots=2)
        two = generate(cfg, params, prompts, max_new=5, temperature=0.8,
                       seed=42, stream="slots", slots=2)
        assert (one == two).all()

    @pytest.mark.parametrize("arch", ["hymba-1.5b", "xlstm-125m"])
    def test_slot_stream_serves_ring_and_recurrent(self, arch):
        """row_state families (ring-buffer hybrid, recurrent xLSTM) serve
        through slot streaming now that admission is a StateStore
        whole-row overwrite after an exact-length prefill: uniform-length
        slot tokens must match the whole-batch path bit-for-bit, even
        when a one-slot table forces reuse."""
        cfg = smoke_config(arch)
        params = transformer.init_params(cfg, jax.random.PRNGKey(2))
        prompts = _prompts(cfg, 3, 10, seed=41)
        batch = generate(cfg, params, prompts, max_new=4)
        for n_slots in (0, 1):
            slot = generate(cfg, params, prompts, max_new=4,
                            stream="slots", slots=n_slots)
            assert (batch == slot).all(), (n_slots, batch, slot)

    @pytest.mark.parametrize("arch", ["hymba-1.5b", "xlstm-125m"])
    def test_slot_stream_ragged_matches_solo_runs(self, arch):
        """Mixed lengths for row_state families: whole-batch ragged stays
        refused (pads would enter the scan state), but slot streaming
        prefills each request at its exact length — every row must match
        a solo run of its unpadded prompt."""
        cfg = smoke_config(arch)
        params = transformer.init_params(cfg, jax.random.PRNGKey(2))
        prompts = _prompts(cfg, 3, 10, seed=43)
        lens = np.array([6, 10, 8], np.int32)
        slot = generate(cfg, params, prompts, max_new=4, prompt_lens=lens,
                        stream="slots", slots=2)
        for i, ln in enumerate(lens):
            solo = generate(cfg, params, prompts[i:i + 1, :ln], max_new=4)
            assert (slot[i] == solo[0]).all(), (i, slot[i], solo[0])

    def test_unknown_stream_refused(self, dense):
        cfg, params = dense
        with pytest.raises(ValueError, match="stream"):
            generate(cfg, params, _prompts(cfg, 2, 8), max_new=2,
                     stream="rows")


class TestDisaggActTransport:
    def test_serve_decode_half_drops_int8_act_transport(self, monkeypatch):
        """Under the serve_decode preset the decode cache is resident (no
        per-step gather), so an int8 act transport would just round the
        whole cache through s8 every step for zero wire saved — generate
        must build the decode step with bf16 transport instead."""
        from repro.dist import sharding as shd
        from repro.launch import serve
        seen = {}
        real = step_lib.make_decode_step

        def spy(cfg, total, act_transport="bf16", kv_storage="bf16"):
            seen["act"] = act_transport
            return real(cfg, total, act_transport, kv_storage)

        monkeypatch.setattr(serve.step_lib, "make_decode_step", spy)
        cfg = smoke_config("paper-lm-100m")
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        pre, dec = serve.make_disagg_meshes(cfg)
        serve.generate(cfg, params, _prompts(cfg, 2, 8), max_new=2,
                       mesh=pre, decode_mesh=dec, act_transport="int8")
        assert seen["act"] == "bf16"
        # custom decode rules keep the caller's transport choice
        serve.generate(cfg, params, _prompts(cfg, 2, 8), max_new=2,
                       mesh=pre, decode_mesh=dec, act_transport="int8",
                       decode_rules=shd.PRESETS["serve_sp"])
        assert seen["act"] == "int8"


class TestStateStoreBleed:
    """Cross-request bleed, at the state level: admitting request B into
    a slot previously held by A must leave the state table bit-identical
    to admitting B into a never-used table — no element of A's recurrent
    state survives, with or without an explicit free_row between."""

    @pytest.mark.parametrize("arch", ["hymba-1.5b", "xlstm-125m"])
    def test_readmission_leaves_no_trace_of_previous_occupant(self, arch):
        from repro.models import registry
        cfg = smoke_config(arch)
        params = transformer.init_params(cfg, jax.random.PRNGKey(2))
        store = registry.state_store(cfg, rows=2, total=16)
        prefill = jax.jit(step_lib.make_prefill_step(cfg))

        def row_state(seed):
            _, c = prefill(params,
                           {"tokens": jnp.asarray(_prompts(cfg, 1, 8,
                                                           seed=seed))})
            return grow_cache(c, store.abstract_row())

        row_a, row_b = row_state(51), row_state(52)

        def leaves_equal(x, y):
            return all(np.array_equal(np.asarray(l1), np.asarray(l2))
                       for l1, l2 in zip(jax.tree.leaves(x),
                                         jax.tree.leaves(y)))

        fresh_b = store.admit_row(store.init_state(), row_b, 0)
        # overwrite-on-admit: A -> B directly
        state = store.admit_row(store.init_state(), row_a, 0)
        assert not leaves_equal(state, fresh_b)       # A is really there
        assert leaves_equal(store.admit_row(state, row_b, 0), fresh_b)
        # explicit eviction: A -> free -> B
        freed = store.free_row(state, 0)
        assert leaves_equal(freed, store.init_state())
        assert leaves_equal(store.admit_row(freed, row_b, 0), fresh_b)

    @pytest.mark.parametrize("arch", ["hymba-1.5b", "xlstm-125m"])
    def test_reused_slot_tokens_match_solo_run(self, arch):
        """End to end: a one-slot table serializes requests through the
        same state row; each request's greedy tokens must still match a
        solo run of its prompt bit-for-bit."""
        cfg = smoke_config(arch)
        params = transformer.init_params(cfg, jax.random.PRNGKey(2))
        prompts = _prompts(cfg, 3, 9, seed=53)
        out = generate(cfg, params, prompts, max_new=3, stream="slots",
                       slots=1)
        for i in range(3):
            solo = generate(cfg, params, prompts[i:i + 1], max_new=3)
            assert (out[i] == solo[0]).all(), (i, out[i], solo[0])


class TestExpertParallelDecode:
    def test_ep_decode_routes_dispatch_through_expert_a2a(self, monkeypatch):
        """Under the ep preset with act_transport="int8", MoE decode must
        dispatch its expert all-to-all payload through the expert_a2a
        tunable op (train/prefill keep the bf16 einsum dispatch)."""
        from repro.dist import sharding as shd
        from repro.launch.mesh import make_local_mesh
        from repro.models import moe as moe_lib

        calls = []
        real = moe_lib.expert_a2a
        monkeypatch.setattr(moe_lib, "expert_a2a",
                            lambda xe, **kw: calls.append(xe.shape)
                            or real(xe, **kw))
        cfg = smoke_config("qwen3-moe-30b-a3b")
        params = transformer.init_params(cfg, jax.random.PRNGKey(3))
        prompts = _prompts(cfg, 2, 8, seed=59)
        mesh = make_local_mesh()
        out = generate(cfg, params, prompts, max_new=3, mesh=mesh,
                       rules=shd.PRESETS["ep"], act_transport="int8")
        assert calls, "decode never dispatched through expert_a2a"
        assert all(len(s) == 4 for s in calls)   # (g, e, c, d) payloads
        assert out.shape == (2, 3)
        assert ((out >= 0) & (out < cfg.vocab)).all()

    def test_bf16_transport_keeps_einsum_dispatch(self, monkeypatch):
        """No int8 transport => no quantized wire: the op must not fire,
        and tokens are bit-identical to the no-mesh path."""
        from repro.models import moe as moe_lib
        calls = []
        monkeypatch.setattr(moe_lib, "expert_a2a",
                            lambda xe, **kw: calls.append(1) or xe)
        cfg = smoke_config("qwen3-moe-30b-a3b")
        params = transformer.init_params(cfg, jax.random.PRNGKey(3))
        prompts = _prompts(cfg, 2, 8, seed=59)
        generate(cfg, params, prompts, max_new=3)
        assert not calls
