"""Multi-prefill-worker fan-in, slot preemption, and the paged slot
cache (launch/serve.py + dist/fanin.py), on any device count.

Arbiter layer (pure host): FIFO-with-priority-classes ordering, aging +
hard promotion (the fleet scheduler's starvation guarantee translated to
admission passes), least-loaded worker assignment that never skips
ahead, justified-only eviction, and NFR2 determinism — the admission
order is a total order with no wall-clock input.

Engine layer (real model, smoke config): evicted-then-readmitted
requests produce greedy tokens bit-identical to an uncontended run
(recompute preemption re-prefills the extended prompt); the paged slot
table bit-matches the unpaged path; requests past the unpaged horizon
are refused loudly (never silently truncated) while ``--paged`` admits
them; pool exhaustion is a loud error. The forced-8-device mesh legs
live in tests/test_serve_disagg.py."""

import jax
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import smoke_config
from repro.dist import fanin
from repro.launch import serve
from repro.models import transformer


def _req(rid, priority=0, plen=4, max_new=4):
    return fanin.Request(rid=rid, prompt=np.zeros((plen,), np.int32),
                         max_new=max_new, priority=priority)


class TestArbiterOrdering:
    def test_fifo_within_class(self):
        arb = fanin.AdmissionArbiter(workers=1, classes=1)
        for rid in (7, 3, 5):
            arb.submit(_req(rid))
        assert [r.rid for r in arb.ordered()] == [7, 3, 5]

    def test_higher_class_beats_enqueue_order(self):
        arb = fanin.AdmissionArbiter(workers=1, classes=3)
        arb.submit(_req(0, priority=2))
        arb.submit(_req(1, priority=1))
        arb.submit(_req(2, priority=0))    # most urgent, submitted last
        assert [r.rid for r in arb.ordered()] == [2, 1, 0]

    def test_aging_boosts_urgency_up_to_the_bound(self):
        arb = fanin.AdmissionArbiter(workers=1, classes=2)
        r = arb.submit(_req(0, priority=1))
        u0 = arb.urgency(r)
        r.skips = arb.promotion_cycles - 1
        assert arb.urgency(r) > u0
        r.skips = arb.promotion_cycles
        capped = arb.urgency(r)
        r.skips = arb.promotion_cycles * 10
        assert arb.urgency(r) == capped    # boost is capped, not unbounded

    def test_hard_promoted_sort_first_oldest_first(self):
        arb = fanin.AdmissionArbiter(workers=1, classes=2)
        worst = arb.submit(_req(0, priority=1))
        older = arb.submit(_req(1, priority=1))
        arb.submit(_req(2, priority=0))    # best class, not promoted
        worst.skips = older.skips = arb.promotion_cycles
        assert [r.rid for r in arb.ordered()] == [0, 1, 2]

    def test_order_is_independent_of_internal_queue_permutation(self):
        """NFR2: the admission order is a total order over request state
        — permuting the arrival bookkeeping cannot permute it."""
        arb = fanin.AdmissionArbiter(workers=2, classes=3)
        for rid in range(9):
            arb.submit(_req(rid, priority=rid % 3))
        want = [r.rid for r in arb.ordered()]
        rng = np.random.RandomState(0)
        for _ in range(5):
            arb.queue = [arb.queue[i]
                         for i in rng.permutation(len(arb.queue))]
            assert [r.rid for r in arb.ordered()] == want

    def test_submit_rejects_priority_outside_classes(self):
        arb = fanin.AdmissionArbiter(workers=1, classes=2)
        with pytest.raises(ValueError, match="priority"):
            arb.submit(_req(0, priority=2))


class TestWorkerAssignment:
    def test_least_loaded_lowest_numbered_wins(self):
        arb = fanin.AdmissionArbiter(workers=3, classes=1, max_inflight=2)
        arb.inflight[0] = 1                # worker 0 already busy
        a, b, c = (arb.submit(_req(r)) for r in range(3))
        arb.assign()
        assert (a.worker, b.worker, c.worker) == (1, 2, 0)

    def test_full_workers_never_skip_ahead(self):
        """When the least-loaded worker is full, assignment stops — a
        later request must not jump an earlier one in arbiter order."""
        arb = fanin.AdmissionArbiter(workers=1, classes=1, max_inflight=1)
        first = arb.submit(_req(0))
        second = arb.submit(_req(1))
        assert [r.rid for r in arb.assign()] == [0]
        assert second.worker == -1
        assert arb.next_admission() is first

    def test_admit_releases_the_worker(self):
        arb = fanin.AdmissionArbiter(workers=1, classes=1, max_inflight=1)
        first = arb.submit(_req(0))
        second = arb.submit(_req(1))
        arb.assign()
        arb.admit(first)
        assert arb.inflight == [0]
        assert [r.rid for r in arb.assign()] == [1]
        assert second.worker == 0


class TestEviction:
    def _occ(self, *prio_seq):
        return [fanin.Occupant(rid=i, priority=p, admit_seq=s)
                for i, (p, s) in enumerate(prio_seq)]

    def test_oldest_picks_earliest_admitted(self):
        arb = fanin.AdmissionArbiter(workers=1, classes=2)
        pending = arb.submit(_req(9, priority=0))
        occ = self._occ((1, 5), (1, 2), (1, 8))
        assert arb.pick_victim(occ, "oldest", pending) == 1

    def test_priority_picks_worst_class_then_oldest(self):
        arb = fanin.AdmissionArbiter(workers=1, classes=3)
        pending = arb.submit(_req(9, priority=0))
        occ = self._occ((1, 0), (2, 6), (2, 3))
        assert arb.pick_victim(occ, "priority", pending) == 2

    def test_equal_rank_pressure_is_refused(self):
        """Unjustified eviction would thrash the table: an equal-class
        pending request ages in the queue instead."""
        arb = fanin.AdmissionArbiter(workers=1, classes=2)
        pending = arb.submit(_req(9, priority=1))
        occ = self._occ((1, 0))
        assert arb.pick_victim(occ, "oldest", pending) is None
        assert arb.pick_victim(occ, "priority", pending) is None

    def test_hard_promotion_justifies_equal_class_eviction(self):
        arb = fanin.AdmissionArbiter(workers=1, classes=2)
        pending = arb.submit(_req(9, priority=1))
        pending.skips = arb.promotion_cycles
        assert arb.pick_victim(self._occ((1, 0)), "oldest", pending) == 0

    def test_none_policy_and_unknown_policy(self):
        arb = fanin.AdmissionArbiter(workers=1, classes=2)
        pending = arb.submit(_req(9, priority=0))
        assert arb.pick_victim(self._occ((1, 0)), "none", pending) is None
        with pytest.raises(ValueError, match="eviction policy"):
            arb.pick_victim(self._occ((1, 0)), "bogus", pending)


class TestStarvationBound:
    @given(st.integers(min_value=2, max_value=4),
           st.integers(min_value=6, max_value=20))
    @settings(max_examples=20, deadline=None)
    def test_worst_class_request_waits_at_most_promotion_cycles(
            self, classes, pressure):
        """The fleet scheduler's starvation guarantee, translated: under
        a continuous stream of most-urgent arrivals, a worst-class
        request is hard-promoted after ``promotion_cycles`` lost passes
        and admitted on the next one — its wait is bounded by the
        promotion bound, not by the pressure."""
        arb = fanin.AdmissionArbiter(workers=1, classes=classes,
                                     max_inflight=64)
        victim = arb.submit(_req(999, priority=classes - 1))
        rid = 0
        waited = None
        for _ in range(pressure + arb.promotion_cycles + 2):
            if rid < pressure:             # fresh class-0 pressure
                arb.submit(_req(rid, priority=0))
                rid += 1
            arb.assign()
            req = arb.next_admission()
            assert req is not None
            arb.admit(req)                 # one slot, freed every pass
            if req is victim:
                waited = req.skips
                break
            arb.age()
        assert waited is not None
        assert waited <= arb.promotion_cycles


# --- engine layer: real model, real tokens -------------------------------

@pytest.fixture(scope="module")
def setup():
    cfg = smoke_config("paper-lm-100m")
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    prompts = rng.randint(0, cfg.vocab, size=(4, 12)).astype(np.int32)
    lens = np.array([7, 12, 9, 11], np.int32)
    golden = serve.generate(cfg, params, prompts, max_new=8,
                            prompt_lens=lens)
    return cfg, params, prompts, lens, golden


class TestFanInEngine:
    def test_uncontended_fanin_matches_batch_path(self, setup):
        cfg, params, prompts, lens, golden = setup
        out = serve.generate(cfg, params, prompts, max_new=8,
                             prompt_lens=lens, workers=2)
        assert (out == golden).all(), (out, golden)
        st_ = serve._generate_slots.last_stats
        assert st_["admissions"] == 4 and st_["evictions"] == 0

    def test_replay_is_deterministic(self, setup):
        """Two identical runs produce identical tokens AND identical
        engine stats — the admission sequence replays exactly."""
        cfg, params, prompts, lens, _ = setup
        a = serve.generate(cfg, params, prompts, max_new=8,
                           prompt_lens=lens, workers=2, slots=2,
                           evict="oldest")
        sa = dict(serve._generate_slots.last_stats)
        b = serve.generate(cfg, params, prompts, max_new=8,
                           prompt_lens=lens, workers=2, slots=2,
                           evict="oldest")
        sb = dict(serve._generate_slots.last_stats)
        sa.pop("transfer_wait_s")          # the one wall-clock stat
        sb.pop("transfer_wait_s")
        assert (a == b).all() and sa == sb

    def test_worker_count_does_not_change_tokens(self, setup):
        cfg, params, prompts, lens, golden = setup
        out = serve.generate(cfg, params, prompts, max_new=8,
                             prompt_lens=lens, workers=3)
        assert (out == golden).all()

    def test_evicted_then_readmitted_matches_uncontended(self, setup):
        """The acceptance criterion: priority preemption on a 2-slot
        table — victims requeue with their emitted tokens, re-prefill on
        readmission, and the greedy continuation bit-matches the
        uncontended run."""
        cfg, params, prompts, lens, golden = setup
        out = serve.generate(cfg, params, prompts, max_new=8,
                             prompt_lens=lens, workers=2, slots=2,
                             evict="priority",
                             priorities=np.array([1, 1, 0, 0], np.int32))
        assert (out == golden).all(), (out, golden)
        st_ = serve._generate_slots.last_stats
        assert st_["evictions"] > 0 and st_["requeues"] > 0

    def test_promotion_driven_oldest_eviction_matches(self, setup):
        """Same-class pressure on a starved table: eviction is justified
        only via hard promotion, and parity still holds."""
        cfg, params, prompts, lens, golden = setup
        out = serve.generate(cfg, params, prompts, max_new=8,
                             prompt_lens=lens, workers=2, slots=2,
                             evict="oldest")
        assert (out == golden).all(), (out, golden)

    def test_sampling_is_refused(self, setup):
        cfg, params, prompts, lens, _ = setup
        with pytest.raises(ValueError, match="greedy"):
            serve.generate(cfg, params, prompts, max_new=8,
                           prompt_lens=lens, workers=2, temperature=0.7)


class TestPagedEngine:
    @pytest.mark.parametrize("page_size", [0, 8])
    def test_paged_matches_unpaged(self, setup, page_size):
        cfg, params, prompts, lens, golden = setup
        out = serve.generate(cfg, params, prompts, max_new=8,
                             prompt_lens=lens, workers=2, paged=True,
                             page_size=page_size)
        assert (out == golden).all(), (out, golden)
        st_ = serve._generate_slots.last_stats
        assert st_["page"] >= 1 and st_["peak_live_pages"] >= 1
        assert st_["hbm_bytes_per_slot"] \
            < st_["dense_hbm_bytes_per_slot"]

    def test_paged_eviction_quantized_storage_matches(self, setup):
        """Pages + preemption + int8-resident storage compose: the paged
        contended run bit-matches the unpaged uncontended fan-in under
        the same storage arm."""
        cfg, params, prompts, lens, _ = setup
        base = serve.generate(cfg, params, prompts, max_new=8,
                              prompt_lens=lens, workers=2,
                              kv_storage="int8")
        out = serve.generate(cfg, params, prompts, max_new=8,
                             prompt_lens=lens, workers=2, slots=2,
                             evict="priority", paged=True, page_size=8,
                             kv_storage="int8",
                             priorities=np.array([1, 1, 0, 0], np.int32))
        assert (out == base).all(), (out, base)
        assert serve._generate_slots.last_stats["evictions"] > 0

    def test_long_request_refused_unpaged_admitted_paged(self, setup):
        """The bugfix, both arms: a request past the unpaged horizon is
        refused loudly (never silently truncated); --paged admits it and
        still matches the horizon-free run."""
        cfg, params, prompts, lens, golden = setup
        with pytest.raises(ValueError, match="refusing to truncate"):
            serve.generate(cfg, params, prompts, max_new=8,
                           prompt_lens=lens, workers=2, horizon=12)
        out = serve.generate(cfg, params, prompts, max_new=8,
                             prompt_lens=lens, workers=2, horizon=12,
                             paged=True, page_size=8)
        assert (out == golden).all(), (out, golden)

    def test_batch_path_refuses_silent_truncation_too(self, setup):
        cfg, params, prompts, lens, _ = setup
        with pytest.raises(ValueError, match="refusing"):
            serve.generate(cfg, params, prompts, max_new=8,
                           prompt_lens=lens, horizon=12)

    def test_pool_exhaustion_is_loud(self, setup):
        cfg, params, prompts, lens, _ = setup
        with pytest.raises(RuntimeError, match="paged pool exhausted"):
            serve.generate(cfg, params, prompts, max_new=8,
                           prompt_lens=lens, workers=2, paged=True,
                           page_size=4, pool_pages=6)

    def test_pool_too_small_for_one_row_refused_upfront(self, setup):
        cfg, params, prompts, lens, _ = setup
        with pytest.raises(ValueError, match="pool of 1 pages"):
            serve.generate(cfg, params, prompts, max_new=8,
                           prompt_lens=lens, workers=2, paged=True,
                           page_size=4, pool_pages=1)


class TestFanInReport:
    def test_report_is_deterministic(self):
        cfg = smoke_config("paper-lm-100m")
        r1 = serve.fanin_report(cfg, 8, 64, decode_step_s=0.01,
                                transfer_s=0.05)
        r2 = serve.fanin_report(cfg, 8, 64, decode_step_s=0.01,
                                transfer_s=0.05)
        assert r1 == r2

    def test_gated_keys_present_and_paged_saves_hbm(self):
        """paged_hbm_bytes_per_slot measurably below the dense
        pad-to-horizon rent — the saving the gate defends."""
        cfg = smoke_config("paper-lm-100m")
        rep = serve.fanin_report(cfg, 8, 64, decode_step_s=0.01,
                                 transfer_s=0.05)
        assert rep["fanin_admission_wait_s"] >= 0.0
        assert rep["fanin_evictions"] >= 0
        assert rep["paged_hbm_bytes_per_slot"] \
            < rep["slot_hbm_bytes_per_slot"]
        assert rep["page"] >= 1 and rep["skipped"] == {}

    def test_contention_produces_queue_wait(self):
        """slots = batch//2 is contention by construction: with a real
        transfer cost the mean admission wait is nonzero."""
        cfg = smoke_config("paper-lm-100m")
        rep = serve.fanin_report(cfg, 8, 64, decode_step_s=0.01,
                                 transfer_s=0.05)
        assert rep["slots"] == 4 and rep["fanin_admission_wait_s"] > 0.0

    def test_recurrent_family_skips_paged_leg(self):
        """No paged capability (recurrent state) => the paged keys are
        absent and the refusal lands under skipped, message intact."""
        cfg = smoke_config("xlstm-125m")
        rep = serve.fanin_report(cfg, 8, 64)
        assert "paged_hbm_bytes_per_slot" not in rep
        assert "--paged" in rep["skipped"]
        assert "paged" in rep["skipped"]["--paged"]
