"""Disaggregated prefill/decode serving on a real multi-device mesh — the
CI ``multidevice`` job runs this under
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.

Prefill compiles sequence-parallel (``serve_sp``) on its own mesh, decode
batch-heavy (``serve_decode``) on a disjoint mesh, and the KV cache is
handed off between them — whole-batch (raw bf16 or a seq-blockwise int8
stream, ``--cache-transfer``) or continuously per request
(``--stream slots``: slot admission into a running decode batch), with
orthogonal int8/f8 *resident* storage arms (``--kv-storage``).
Assertions mirror the acceptance criteria: resolved decode-side
shardings, s8 on the transfer wire (< bf16/1.5, HLO-parsed),
token-for-token colocated-vs-slot-streamed equivalence for the bf16
stream (slots freed and reused without cross-request bleed), logit
tolerance for int8/f8 storage, f8 residency exactly half of bf16, and
the full transfer x storage x block dryrun report (per-slot wire,
overlap fractions, tuned point). Skipped below 8 devices."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import smoke_config
from repro.dist import sharding as shd
from repro.launch import analysis
from repro.launch import serve
from repro.models import transformer
from repro.train import step as step_lib

pytestmark = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")

BATCH, TOTAL = 8, 512


@pytest.fixture(scope="module")
def cfg():
    return smoke_config("paper-lm-100m")


@pytest.fixture(scope="module")
def mesh():
    """The colocated (4, 2) mesh of the acceptance criteria."""
    return jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


@pytest.fixture(scope="module")
def disagg_meshes(cfg):
    return serve.make_disagg_meshes(cfg)


@pytest.fixture(scope="module")
def setup(cfg):
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    prompts = rng.randint(0, cfg.vocab, size=(8, 16)).astype(np.int32)
    lens = rng.randint(8, 17, size=(8,)).astype(np.int32)
    return params, prompts, lens


class TestDisaggMeshes:
    def test_meshes_are_disjoint_halves(self, disagg_meshes):
        pre, dec = disagg_meshes
        pre_ids = {d.id for d in pre.devices.flat}
        dec_ids = {d.id for d in dec.devices.flat}
        assert pre_ids.isdisjoint(dec_ids)
        assert len(pre_ids) == len(dec_ids) == jax.device_count() // 2

    def test_serve_decode_cache_resident_not_seq_sharded(self, cfg,
                                                         disagg_meshes):
        """serve_decode: batch -> data, sequence REPLICATED (no per-step
        cache gather) — read back from committed arrays."""
        _, dec = disagg_meshes
        rules = shd.PRESETS["serve_decode"]
        cache = transformer.init_cache(cfg, BATCH, TOTAL)
        shards = shd.tree_shardings(
            transformer.abstract_cache(cfg, BATCH, TOTAL),
            transformer.cache_axes(cfg, BATCH, TOTAL), dec, rules)
        placed = jax.device_put(cache, shards)
        data = dec.shape["data"]
        for name in ("k", "v"):
            leaf = placed[name]          # (layers, B, S, Hkv, hd)
            assert leaf.sharding.spec == P(None, "data")
            local = leaf.addressable_shards[0].data
            # full sequence resident per batch shard
            assert local.shape[1:3] == (BATCH // data, TOTAL)


def _transfer_hlo(cfg, mesh, mode):
    c_abs = transformer.abstract_cache(cfg, BATCH, TOTAL)
    c_axes = transformer.cache_axes(cfg, BATCH, TOTAL)
    pre = shd.tree_shardings(c_abs, c_axes, mesh, shd.PRESETS["serve_sp"])
    dec = shd.tree_shardings(c_abs, c_axes, mesh,
                             shd.PRESETS["serve_decode"])
    fn = serve.make_cache_transfer_step(cfg, BATCH, TOTAL, mode)
    with shd.axis_rules(mesh, shd.PRESETS["serve_decode"]):
        return jax.jit(fn, in_shardings=(pre,), out_shardings=dec
                       ).lower(c_abs).compile().as_text()


class TestCacheStreamWire:
    """The transfer acceptance gate: the serve_sp -> serve_decode cache
    reshard moves s8 under the int8 stream, < 1/1.5 the bf16 wire."""

    @pytest.fixture(scope="class")
    def coll(self, cfg, mesh):
        return {t: analysis.hlo_collective_bytes(_transfer_hlo(cfg, mesh, t))
                for t in ("bf16", "int8")}

    def test_bf16_transfer_reshards_and_moves_no_s8(self, coll):
        assert coll["bf16"]["total_wire_bytes_bf16eq"] > 0
        assert coll["bf16"]["total_wire_bytes_bf16eq_s8"] == 0

    def test_int8_transfer_wire_is_mostly_s8(self, coll):
        s8 = coll["int8"]["total_wire_bytes_bf16eq_s8"]
        assert s8 > 0
        assert s8 > coll["int8"]["total_wire_bytes_bf16eq"] / 2

    def test_int8_transfer_below_bf16_over_1p5(self, coll):
        wire = {t: c["total_wire_bytes_bf16eq"] for t, c in coll.items()}
        assert wire["int8"] <= wire["bf16"] / 1.5, wire


class TestDisaggEquivalence:
    def test_bf16_stream_token_identical_to_colocated(self, cfg, mesh,
                                                      disagg_meshes, setup):
        """The acceptance criterion: splitting prefill/decode onto
        separate meshes (bf16 handoff) must not flip a single greedy
        token vs colocated serve_sp serving."""
        params, prompts, lens = setup
        pre, dec = disagg_meshes
        colo = serve.generate(cfg, params, prompts, max_new=12,
                              prompt_lens=lens, mesh=mesh)
        dis = serve.generate(cfg, params, prompts, max_new=12,
                             prompt_lens=lens, mesh=pre, decode_mesh=dec)
        assert (colo == dis).all(), (colo, dis)

    def test_int8_stream_int8_storage_tracks_bf16(self, cfg, disagg_meshes,
                                                  setup):
        """The fully quantized pipeline (s8 handoff + s8-resident cache)
        is lossy; on the smoke config it must still agree on (almost)
        every row with the bf16 pipeline."""
        params, prompts, lens = setup
        pre, dec = disagg_meshes
        base = serve.generate(cfg, params, prompts, max_new=12,
                              prompt_lens=lens, mesh=pre, decode_mesh=dec)
        quant = serve.generate(cfg, params, prompts, max_new=12,
                               prompt_lens=lens, mesh=pre, decode_mesh=dec,
                               cache_transfer="int8", kv_storage="int8")
        rows_equal = (base == quant).all(axis=1)
        assert rows_equal.mean() >= 0.5, (base, quant)


class TestInt8StorageLogits:
    def test_int8_storage_matches_bf16_logits(self, cfg, mesh):
        """kv_storage="int8" decode matches the bf16-resident decode's
        logits within quantization tolerance, on the decode mesh."""
        params = transformer.init_params(cfg, jax.random.PRNGKey(1))
        b, s0, total = 8, 16, 32
        rules = shd.PRESETS["serve_decode"]
        prompts = np.random.RandomState(1).randint(
            0, cfg.vocab, size=(b, s0)).astype(np.int32)
        with shd.axis_rules(mesh, rules):
            p_shard = shd.tree_shardings(transformer.abstract_params(cfg),
                                         transformer.param_axes(cfg),
                                         mesh, rules)
            placed = jax.device_put(params, p_shard)
            _, cache = jax.jit(step_lib.make_prefill_step(cfg))(
                placed, {"tokens": jnp.asarray(prompts)})
            cache = serve.grow_cache(
                cache, transformer.abstract_cache(cfg, b, total))
            tok = jnp.full((b, 1), 7, jnp.int32)
            batch = {"tokens": tok, "pos": jnp.asarray(s0, jnp.int32)}
            logits = {}
            for storage in ("bf16", "int8"):
                c = cache
                if storage == "int8":
                    c = jax.jit(transformer.quantize_cache_int8)(cache)
                fn = step_lib.make_decode_step(cfg, total, "bf16", storage)
                lg, _ = jax.jit(fn)(placed, c, batch)
                logits[storage] = np.asarray(lg, np.float32)
        diff = np.abs(logits["bf16"] - logits["int8"]).max()
        scale = max(np.abs(logits["bf16"]).max(), 1.0)
        assert diff / scale < 0.05, diff
        agree = (logits["bf16"].argmax(-1) == logits["int8"].argmax(-1))
        assert agree.mean() >= 0.9


class TestSlotStreaming:
    """Continuous cross-batch disaggregation on the real meshes: the
    acceptance criterion — slot-streamed serving (bf16 stream) produces
    greedy tokens identical to colocated serving, slots are freed and
    reused across admissions without cross-request cache bleed."""

    def test_slot_stream_token_identical_to_colocated(self, cfg, mesh,
                                                      disagg_meshes, setup):
        params, prompts, lens = setup
        pre, dec = disagg_meshes
        colo = serve.generate(cfg, params, prompts, max_new=12,
                              prompt_lens=lens, mesh=mesh)
        slot = serve.generate(cfg, params, prompts, max_new=12,
                              prompt_lens=lens, mesh=pre, decode_mesh=dec,
                              stream="slots")
        assert (colo == slot).all(), (colo, slot)

    def test_slots_freed_and_reused_without_bleed(self, cfg, mesh,
                                                  disagg_meshes, setup):
        """slots=3 < batch=8 forces five admissions into freed rows —
        every later occupant's tokens must still match the whole-batch
        run (admission overwrites the entire slot row, so no trace of
        the previous request survives)."""
        params, prompts, lens = setup
        pre, dec = disagg_meshes
        colo = serve.generate(cfg, params, prompts, max_new=12,
                              prompt_lens=lens, mesh=mesh)
        slot = serve.generate(cfg, params, prompts, max_new=12,
                              prompt_lens=lens, mesh=pre, decode_mesh=dec,
                              stream="slots", slots=3)
        assert (colo == slot).all(), (colo, slot)
        assert serve._generate_slots.last_stats["admissions"] == 8

    def test_quantized_slot_pipeline_tracks_bf16(self, cfg, disagg_meshes,
                                                 setup):
        """The fully continuous quantized pipeline — s8 slice stream into
        an f8-resident running cache — stays row-wise close to bf16."""
        params, prompts, lens = setup
        pre, dec = disagg_meshes
        base = serve.generate(cfg, params, prompts, max_new=12,
                              prompt_lens=lens, mesh=pre, decode_mesh=dec,
                              stream="slots")
        quant = serve.generate(cfg, params, prompts, max_new=12,
                               prompt_lens=lens, mesh=pre, decode_mesh=dec,
                               stream="slots", cache_transfer="int8",
                               kv_storage="f8")
        rows_equal = (base == quant).all(axis=1)
        assert rows_equal.mean() >= 0.5, (base, quant)


class TestF8StorageOnMesh:
    def test_f8_storage_matches_bf16_logits(self, cfg, mesh):
        """kv_storage="f8" decode matches the bf16-resident decode's
        logits within e4m3 tolerance, on the decode mesh."""
        params = transformer.init_params(cfg, jax.random.PRNGKey(1))
        b, s0, total = 8, 16, 32
        rules = shd.PRESETS["serve_decode"]
        prompts = np.random.RandomState(1).randint(
            0, cfg.vocab, size=(b, s0)).astype(np.int32)
        with shd.axis_rules(mesh, rules):
            p_shard = shd.tree_shardings(transformer.abstract_params(cfg),
                                         transformer.param_axes(cfg),
                                         mesh, rules)
            placed = jax.device_put(params, p_shard)
            _, cache = jax.jit(step_lib.make_prefill_step(cfg))(
                placed, {"tokens": jnp.asarray(prompts)})
            cache = serve.grow_cache(
                cache, transformer.abstract_cache(cfg, b, total))
            tok = jnp.full((b, 1), 7, jnp.int32)
            batch = {"tokens": tok, "pos": jnp.asarray(s0, jnp.int32)}
            logits = {}
            for storage in ("bf16", "f8"):
                c = jax.jit(lambda x, s=storage:
                            transformer.quantize_cache(x, s))(cache)
                fn = step_lib.make_decode_step(cfg, total, "bf16", storage)
                lg, _ = jax.jit(fn)(placed, c, batch)
                logits[storage] = np.asarray(lg, np.float32)
        diff = np.abs(logits["bf16"] - logits["f8"]).max()
        scale = max(np.abs(logits["bf16"]).max(), 1.0)
        assert diff / scale < 0.08, diff
        agree = (logits["bf16"].argmax(-1) == logits["f8"].argmax(-1))
        assert agree.mean() >= 0.9


class TestDisaggDryrunReport:
    @pytest.fixture(scope="class")
    def report(self, cfg, mesh):
        return serve.disagg_decode_report(cfg, BATCH, TOTAL, mesh,
                                          blocks=(256, 128))

    def test_all_six_combinations_reported(self, report):
        assert set(report["cells"]) == {
            f"{t}x{s}" for t in ("bf16", "int8")
            for s in ("bf16", "int8", "f8")}
        assert report["unsupported_storage"] == []
        for cell in report["cells"].values():
            assert cell["collective_s"] >= 0
            assert cell["cache_resident_bytes_per_device"] > 0
            assert 0.0 <= cell["slot_stream_overlap_frac"] <= 1.0

    def test_quantized_storage_shrinks_resident_bytes(self, report):
        cells = report["cells"]
        bf16 = cells["bf16xbf16"]["cache_resident_bytes_per_device"]
        assert cells["bf16xint8"]["cache_resident_bytes_per_device"] < bf16
        # f8 is scale-free: exactly half the bf16 bytes — the acceptance
        # criterion's residency claim
        assert cells["bf16xf8"]["cache_resident_bytes_per_device"] \
            == bf16 // 2

    def test_int8_transfer_shrinks_transfer_wire(self, report):
        cells = report["cells"]
        assert cells["int8xbf16"]["transfer_wire_bytes_bf16eq"] \
            <= cells["bf16xbf16"]["transfer_wire_bytes_bf16eq"] / 1.5
        assert cells["int8xbf16"]["transfer_wire_bytes_bf16eq_s8"] > 0

    def test_slot_stream_wire_is_per_request_sized(self, report):
        """The per-slot admission program ships ONE request's slice: its
        wire is ~1/BATCH of the whole-batch transfer, s8-dominant under
        the int8 stream and < bf16/1.5."""
        ss = report["slot_stream"]
        cells = report["cells"]
        for t in ("bf16", "int8"):
            assert 0 < ss[t]["wire_bytes_bf16eq"] \
                <= cells[f"{t}xbf16"]["transfer_wire_bytes_bf16eq"] / 2
        assert ss["int8"]["wire_bytes_bf16eq_s8"] \
            > ss["int8"]["wire_bytes_bf16eq"] / 2
        assert ss["int8"]["wire_bytes_bf16eq"] \
            <= ss["bf16"]["wire_bytes_bf16eq"] / 1.5

    def test_block_sweep_and_tuned_point(self, report):
        """Smaller stream blocks mean more f32 scales on the wire; the
        hillclimb's pick is a member of the swept space."""
        sweep = report["block_sweep"]["int8"]
        assert set(sweep) == {128, 256}
        assert sweep[128]["transfer_wire_bytes_bf16eq"] \
            >= sweep[256]["transfer_wire_bytes_bf16eq"]
        tuned = report["tuned"]
        assert tuned["point"]["cache_transfer"] in ("bf16", "int8")
        assert tuned["point"]["kv_storage"] in ("bf16", "int8", "f8")
        assert tuned["point"]["block"] in (128, 256)
        assert tuned["collective_s"] > 0
        assert tuned["evaluations"] >= 1


class TestFanInOnMesh:
    """The fan-in acceptance criterion on the forced 8-device mesh:
    paged + preempted greedy tokens bit-match the unpaged uncontended
    path, colocated AND disaggregated across per-worker prefill meshes
    (serve.make_fanin_meshes)."""

    @pytest.fixture(scope="class")
    def fanin_meshes(self, cfg):
        return serve.make_fanin_meshes(cfg, workers=2)

    @pytest.fixture(scope="class")
    def golden(self, cfg, mesh, setup):
        params, prompts, lens = setup
        return serve.generate(cfg, params, prompts, max_new=12,
                              prompt_lens=lens, mesh=mesh)

    def test_worker_meshes_partition_the_prefill_half(self, fanin_meshes):
        pres, dec = fanin_meshes
        assert len(pres) == 2
        dec_ids = {d.id for d in dec.devices.flat}
        pre_ids = [frozenset(d.id for d in m.devices.flat) for m in pres]
        assert pre_ids[0] and pre_ids[1]
        assert pre_ids[0].isdisjoint(pre_ids[1])
        for ids in pre_ids:
            assert ids.isdisjoint(dec_ids)

    def test_paged_preempted_matches_colocated(self, cfg, mesh, setup,
                                               golden):
        """slots=3 < batch=8 forces preemption (class pressure + the
        promotion bound); the paged, contended run bit-matches the
        dense uncontended one."""
        params, prompts, lens = setup
        out = serve.generate(cfg, params, prompts, max_new=12,
                             prompt_lens=lens, mesh=mesh, workers=2,
                             slots=3, evict="priority", paged=True,
                             priorities=(np.arange(8) % 2).astype(np.int32))
        assert (out == golden).all(), (out, golden)
        st = serve._generate_slots.last_stats
        assert st["evictions"] > 0
        assert st["hbm_bytes_per_slot"] < st["dense_hbm_bytes_per_slot"]

    def test_paged_preempted_matches_across_fanin_meshes(
            self, cfg, fanin_meshes, setup, golden):
        """Two real prefill worker meshes feeding the decode-mesh slot
        table: live pages ship across meshes, victims re-prefill on
        their own worker, tokens still bit-match."""
        params, prompts, lens = setup
        pres, dec = fanin_meshes
        out = serve.generate(cfg, params, prompts, max_new=12,
                             prompt_lens=lens, mesh=pres[0],
                             prefill_meshes=pres, decode_mesh=dec,
                             workers=2, slots=3, evict="oldest",
                             paged=True)
        assert (out == golden).all(), (out, golden)
        assert serve._generate_slots.last_stats["admissions"] >= 8
