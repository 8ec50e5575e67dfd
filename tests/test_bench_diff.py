"""The CI bench-trajectory gate (scripts/bench_diff.py): synthetic
trajectories prove the bench-smoke job fails on an injected >=15%
collective_s (or roofline_fraction) regression, passes within tolerance,
and tolerates a missing baseline on the first run."""

import importlib.util
import json
import os

import pytest

_SPEC = importlib.util.spec_from_file_location(
    "bench_diff",
    os.path.join(os.path.dirname(__file__), "..", "scripts", "bench_diff.py"))
bench_diff = importlib.util.module_from_spec(_SPEC)
_SPEC.loader.exec_module(bench_diff)


def _rec(arch="paper-lm-100m", shape="train_4k", mesh="16x16",
         preset="baseline", grad_transport="bf16", act_transport=None,
         collective_s=0.1, roofline_fraction=0.5, status="ok",
         microbatches=8, remat_block=1, capacity_factor=1.25):
    return {
        "arch": arch, "shape": shape, "mesh": mesh, "preset": preset,
        "grad_transport": grad_transport, "act_transport": act_transport,
        "microbatches": microbatches, "remat_block": remat_block,
        "capacity_factor": capacity_factor,
        "status": status,
        "roofline": {"collective_s": collective_s,
                     "roofline_fraction": roofline_fraction},
    }


def _traj(path, records):
    with open(path, "w") as f:
        json.dump({"cells": len(records), "rows": [], "records": records}, f)
    return str(path)


class TestDiffTrajectories:
    def test_no_regression_within_threshold(self):
        base = [_rec(collective_s=0.100), _rec(shape="decode_32k",
                                               collective_s=0.060)]
        cur = [_rec(collective_s=0.110),   # +10% < 15%: fine
               _rec(shape="decode_32k", collective_s=0.055)]  # improvement
        res = bench_diff.diff_trajectories(cur, base, threshold=0.15)
        assert res["compared"] == 2
        assert res["regressions"] == []

    def test_collective_s_regression_fails(self):
        base = [_rec(collective_s=0.100)]
        cur = [_rec(collective_s=0.120)]   # +20% > 15%
        res = bench_diff.diff_trajectories(cur, base, threshold=0.15)
        assert len(res["regressions"]) == 1
        r = res["regressions"][0]
        assert r["metric"] == "collective_s"
        assert r["change"] == pytest.approx(0.20, abs=1e-6)

    def test_roofline_fraction_drop_fails(self):
        """Higher-is-better metric: a drop is the regression direction."""
        base = [_rec(roofline_fraction=0.50)]
        cur = [_rec(roofline_fraction=0.40)]   # -20%
        res = bench_diff.diff_trajectories(cur, base)
        assert [r["metric"] for r in res["regressions"]] \
            == ["roofline_fraction"]
        # and a roofline_fraction *gain* never trips the gate
        res2 = bench_diff.diff_trajectories([_rec(roofline_fraction=0.9)],
                                            base)
        assert res2["regressions"] == []

    def test_threshold_is_configurable(self):
        base = [_rec(collective_s=0.100)]
        cur = [_rec(collective_s=0.110)]
        assert bench_diff.diff_trajectories(cur, base,
                                            threshold=0.05)["regressions"]
        assert not bench_diff.diff_trajectories(cur, base,
                                                threshold=0.15)["regressions"]

    def test_cells_matched_by_full_variant_key(self):
        """An int8 serve cell never diffs against its bf16 sibling."""
        base = [_rec(shape="decode_32k", grad_transport=None,
                     act_transport="bf16", collective_s=0.060)]
        cur = [_rec(shape="decode_32k", grad_transport=None,
                    act_transport="int8", collective_s=0.090)]
        res = bench_diff.diff_trajectories(cur, base)
        assert res["compared"] == 0
        assert res["regressions"] == []
        assert len(res["only_current"]) == 1

    def test_hyperparameter_variants_never_collide(self):
        """mb/rb/cf sweeps of the same cell are distinct gate keys — a
        current mb4 cell must not diff against an mb8 baseline."""
        base = [_rec(microbatches=8, collective_s=0.100)]
        cur = [_rec(microbatches=4, collective_s=0.200)]
        res = bench_diff.diff_trajectories(cur, base)
        assert res["compared"] == 0 and res["regressions"] == []
        assert bench_diff.cell_key(_rec(remat_block=2)) \
            != bench_diff.cell_key(_rec(remat_block=1))
        assert bench_diff.cell_key(_rec(capacity_factor=2.0)) \
            != bench_diff.cell_key(_rec())

    def test_non_ok_and_malformed_cells_are_ignored(self):
        base = [_rec(collective_s=0.1),
                _rec(shape="prefill_8k", status="skip")]
        cur = [_rec(collective_s=0.1),
               _rec(shape="prefill_8k", status="error"),
               {"arch": "x", "status": "ok"}]      # no roofline dict
        res = bench_diff.diff_trajectories(cur, base)
        assert res["compared"] == 1
        assert res["regressions"] == []


def _disagg_rec(**roofline):
    r = _rec(shape="decode_32k", grad_transport=None, act_transport="bf16")
    r["roofline"].update(roofline)
    return r


class TestSlotStreamAndF8Keys:
    """The continuous-streaming / f8-arm roofline keys are first-class
    gate metrics: per-slot wire bytes and transfer time regress when they
    grow, overlap efficiency when it shrinks, the f8 storage arm like any
    other combo."""

    def test_all_new_keys_are_gated(self):
        for t in ("bf16", "int8"):
            assert bench_diff.METRICS[f"slot_stream_transfer_s_{t}"] \
                == "lower"
            assert bench_diff.METRICS[f"slot_stream_wire_bytes_{t}"] \
                == "lower"
            for s in ("bf16", "int8", "f8"):
                assert bench_diff.METRICS[f"disagg_collective_s_{t}x{s}"] \
                    == "lower"
                assert bench_diff.METRICS[
                    f"slot_stream_overlap_frac_{t}x{s}"] == "higher"
        assert bench_diff.METRICS["disagg_decode_step_s_f8"] == "lower"
        assert bench_diff.METRICS["disagg_tuned_collective_s"] == "lower"

    def test_overlap_frac_drop_fails(self):
        """Overlap efficiency is higher-is-better: transfer time that
        stops hiding behind decode steps is a regression."""
        base = [_disagg_rec(slot_stream_overlap_frac_int8xf8=0.40)]
        cur = [_disagg_rec(slot_stream_overlap_frac_int8xf8=0.30)]  # -25%
        res = bench_diff.diff_trajectories(cur, base)
        assert [r["metric"] for r in res["regressions"]] \
            == ["slot_stream_overlap_frac_int8xf8"]
        # a gain never trips the gate
        res2 = bench_diff.diff_trajectories(
            [_disagg_rec(slot_stream_overlap_frac_int8xf8=0.9)], base)
        assert res2["regressions"] == []

    def test_slot_wire_and_f8_decode_step_growth_fails(self):
        base = [_disagg_rec(slot_stream_wire_bytes_int8=1000,
                            disagg_decode_step_s_f8=0.010)]
        cur = [_disagg_rec(slot_stream_wire_bytes_int8=1300,   # +30%
                           disagg_decode_step_s_f8=0.013)]     # +30%
        res = bench_diff.diff_trajectories(cur, base)
        assert sorted(r["metric"] for r in res["regressions"]) \
            == ["disagg_decode_step_s_f8", "slot_stream_wire_bytes_int8"]


class TestDisappearedKeys:
    """A gated metric the baseline has but the current artifact lost must
    fail loudly — before this rule a renamed roofline key silently
    stopped being gated."""

    def test_disappeared_metric_fails(self):
        base = [_disagg_rec(disagg_collective_s_bf16xbf16=0.06,
                            slot_stream_wire_bytes_int8=1000)]
        cur = [_disagg_rec(slot_stream_wire_bytes_int8=1000)]
        res = bench_diff.diff_trajectories(cur, base)
        assert res["regressions"] == []
        assert [m["metric"] for m in res["missing_metrics"]] \
            == ["disagg_collective_s_bf16xbf16"]

    def test_metric_absent_from_both_sides_is_skipped(self):
        """Old baselines without the new keys stay comparable."""
        res = bench_diff.diff_trajectories([_disagg_rec()], [_disagg_rec()])
        assert res["compared"] == 1
        assert res["missing_metrics"] == []

    def test_new_metric_only_in_current_is_fine(self):
        """Sweeps legitimately grow: a key the baseline never had is not
        a disappearance."""
        res = bench_diff.diff_trajectories(
            [_disagg_rec(slot_stream_overlap_frac_int8xf8=0.4)],
            [_disagg_rec()])
        assert res["missing_metrics"] == [] and res["regressions"] == []

    def test_ungated_key_disappearing_is_ignored(self):
        base = [_disagg_rec(some_debug_number=1.0)]
        res = bench_diff.diff_trajectories([_disagg_rec()], base)
        assert res["missing_metrics"] == []

    def test_disappeared_metric_exits_nonzero(self, tmp_path):
        base = _traj(tmp_path / "base.json",
                     [_disagg_rec(disagg_collective_s_bf16xbf16=0.06)])
        cur = _traj(tmp_path / "cur.json", [_disagg_rec()])
        assert bench_diff.main(["--current", cur, "--baseline", base]) == 1


def _fleet_rec(shape="fleet_48t_3c", **roofline):
    r = {"arch": "fleet-sim", "shape": shape, "mesh": None,
         "preset": "fleet", "grad_transport": None, "act_transport": None,
         "microbatches": None, "remat_block": None, "capacity_factor": None,
         "status": "ok",
         "roofline": {"fleet_p99_query_s": 2.0,
                      "fleet_file_count_final": 5000.0,
                      "fleet_gbhr_total": 3.0,
                      "fleet_starvation_max_cycles": 2.0}}
    r["roofline"].update(roofline)
    return r


class TestFleetKeys:
    """The fleet-sim artifact keys are gated lower-is-better: the storm is
    seeded, so metric growth is a scheduler behavior change, not noise."""

    def test_fleet_keys_are_gated_lower(self):
        for m in ("fleet_p99_query_s", "fleet_file_count_final",
                  "fleet_gbhr_total", "fleet_starvation_max_cycles"):
            assert bench_diff.METRICS[m] == "lower"

    def test_p99_and_file_count_growth_fails(self):
        base = [_fleet_rec()]
        cur = [_fleet_rec(fleet_p99_query_s=2.6,          # +30%
                          fleet_file_count_final=6500.0)]  # +30%
        res = bench_diff.diff_trajectories(cur, base)
        assert sorted(r["metric"] for r in res["regressions"]) \
            == ["fleet_file_count_final", "fleet_p99_query_s"]

    def test_starvation_bound_growth_fails(self):
        """An aging-invariant break (max skip cycles up 2 -> 3) trips the
        gate even though every latency number held."""
        res = bench_diff.diff_trajectories(
            [_fleet_rec(fleet_starvation_max_cycles=3.0)], [_fleet_rec()])
        assert [r["metric"] for r in res["regressions"]] \
            == ["fleet_starvation_max_cycles"]

    def test_improvement_passes(self):
        res = bench_diff.diff_trajectories(
            [_fleet_rec(fleet_p99_query_s=1.0, fleet_file_count_final=3000.0,
                        fleet_gbhr_total=2.0)],
            [_fleet_rec()])
        assert res["regressions"] == [] and res["missing_metrics"] == []

    def test_smoke_and_sweep_cells_never_collide(self):
        """The shape encodes the fleet size: the PR-smoke 48-table cell
        must not diff against the nightly 2000-table storm."""
        base = [_fleet_rec(shape="fleet_2000t_4c",
                           fleet_file_count_final=400_000.0)]
        cur = [_fleet_rec(shape="fleet_48t_3c")]
        res = bench_diff.diff_trajectories(cur, base)
        assert res["compared"] == 0 and res["regressions"] == []

    def test_lost_fleet_key_fails(self, tmp_path):
        base = _traj(tmp_path / "base.json", [_fleet_rec()])
        rec = _fleet_rec()
        del rec["roofline"]["fleet_starvation_max_cycles"]
        cur = _traj(tmp_path / "cur.json", [rec])
        assert bench_diff.main(["--current", cur, "--baseline", base]) == 1


def _retention_rec(**roofline):
    r = _fleet_rec(shape="fleet_48t_3c_ret",
                   fleet_rows_dropped=1_000_000.0,
                   fleet_retention_bytes_rewritten=5e9)
    r["roofline"].update(roofline)
    return r


class TestRetentionKeys:
    """PR 8's retention cells: rows_dropped is gated HIGHER (a change that
    starves deletes shrinks it), tier-2 rewrite bytes LOWER (aligned
    deletes must stay metadata-only)."""

    def test_directions(self):
        assert bench_diff.METRICS["fleet_rows_dropped"] == "higher"
        assert bench_diff.METRICS["fleet_retention_bytes_rewritten"] \
            == "lower"

    def test_rows_dropped_shrinking_fails(self):
        res = bench_diff.diff_trajectories(
            [_retention_rec(fleet_rows_dropped=700_000.0)],   # -30%
            [_retention_rec()])
        assert [r["metric"] for r in res["regressions"]] \
            == ["fleet_rows_dropped"]

    def test_rewrite_bytes_growth_fails(self):
        """A router change that sends boundary-aligned deletes to tier-2
        rewrites shows up as byte growth and trips the gate."""
        res = bench_diff.diff_trajectories(
            [_retention_rec(fleet_retention_bytes_rewritten=7e9)],  # +40%
            [_retention_rec()])
        assert [r["metric"] for r in res["regressions"]] \
            == ["fleet_retention_bytes_rewritten"]

    def test_more_deletes_fewer_bytes_passes(self):
        res = bench_diff.diff_trajectories(
            [_retention_rec(fleet_rows_dropped=2_000_000.0,
                            fleet_retention_bytes_rewritten=1e9)],
            [_retention_rec()])
        assert res["regressions"] == []

    def test_retention_cell_is_its_own_lineage(self, tmp_path):
        """Turning --retention on starts a fresh `_ret` cell; the old
        non-retention cell disappearing entirely is NOT a lost-key
        failure (cells present on only one side never diff)."""
        base = _traj(tmp_path / "base.json",
                     [_fleet_rec(shape="fleet_48t_3c")])
        cur = _traj(tmp_path / "cur.json", [_retention_rec()])
        assert bench_diff.main(["--current", cur, "--baseline", base]) == 0


def _kernel_rec(shape="compact_pack:nsrc128_nout128:int32", **roofline):
    r = {"arch": "kernel", "shape": shape, "mesh": None,
         "preset": "kernel-quick", "grad_transport": None,
         "act_transport": None, "microbatches": None, "remat_block": None,
         "capacity_factor": None, "status": "ok",
         "roofline": {"kernel_compact_pack_default_s": 0.004,
                      "kernel_compact_pack_tuned_s": 0.001}}
    r["roofline"].update(roofline)
    return r


class TestKernelKeys:
    """Tunable-kernel cells (bench_kernels --json): the tuned step time per
    op and the fused filter path's time + plan-derived HBM traffic are
    gated lower-is-better."""

    def test_kernel_keys_are_gated_lower(self):
        for op in ("compact_pack", "flash_attn", "decode_attn",
                   "paged_attn", "rmsnorm", "expert_a2a", "expert_gmm"):
            assert bench_diff.METRICS[f"kernel_{op}_tuned_s"] == "lower"
        assert bench_diff.METRICS["kernel_compact_filter_s"] == "lower"
        assert bench_diff.METRICS["kernel_compact_filter_hbm_bytes"] \
            == "lower"

    def test_every_registered_op_has_a_gated_tuned_key(self):
        """New kernels registered on repro.kernels.api must join the
        bench gate — a registered op whose kernel_<op>_tuned_s key is
        absent from METRICS would emit ungated trajectory points."""
        from repro.kernels import api
        for name in api.ops():
            assert bench_diff.METRICS.get(f"kernel_{name}_tuned_s") \
                == "lower", name

    def test_expert_a2a_tuned_regression_fails(self):
        base = [_kernel_rec(kernel_expert_a2a_tuned_s=0.001)]
        cur = [_kernel_rec(kernel_expert_a2a_tuned_s=0.0013)]  # +30%
        res = bench_diff.diff_trajectories(cur, base)
        assert [r["metric"] for r in res["regressions"]] \
            == ["kernel_expert_a2a_tuned_s"]

    def test_tuned_regression_fails_default_drift_does_not(self):
        """The serving path reads the tuned point, so only the tuned
        trajectory gates; the default timing is context."""
        res = bench_diff.diff_trajectories(
            [_kernel_rec(kernel_compact_pack_tuned_s=0.0013)],  # +30%
            [_kernel_rec()])
        assert [r["metric"] for r in res["regressions"]] \
            == ["kernel_compact_pack_tuned_s"]
        res2 = bench_diff.diff_trajectories(
            [_kernel_rec(kernel_compact_pack_default_s=0.04)],
            [_kernel_rec()])
        assert res2["regressions"] == []

    def test_filter_hbm_bytes_growth_fails(self):
        """The HBM model is plan-derived (deterministic): a plan change
        that starts re-reading dropped rows must fail even if the
        stopwatch happens to be quiet."""
        fshape = "compact_filter:n128_drop50"
        base = [_kernel_rec(shape=fshape,
                            kernel_compact_filter_s=0.005,
                            kernel_compact_filter_hbm_bytes=786432.0)]
        cur = [_kernel_rec(shape=fshape,
                           kernel_compact_filter_s=0.005,
                           kernel_compact_filter_hbm_bytes=1800000.0)]
        res = bench_diff.diff_trajectories(cur, base)
        assert [r["metric"] for r in res["regressions"]] \
            == ["kernel_compact_filter_hbm_bytes"]

    def test_quick_and_full_presets_never_collide(self):
        base = [_kernel_rec()]
        cur = [_kernel_rec()]
        cur[0]["preset"] = "kernel-full"
        cur[0]["roofline"]["kernel_compact_pack_tuned_s"] = 0.9
        res = bench_diff.diff_trajectories(cur, base)
        assert res["compared"] == 0 and res["regressions"] == []

    def test_lost_tuned_key_fails(self, tmp_path):
        base = _traj(tmp_path / "base.json", [_kernel_rec()])
        rec = _kernel_rec()
        del rec["roofline"]["kernel_compact_pack_tuned_s"]
        cur = _traj(tmp_path / "cur.json", [rec])
        assert bench_diff.main(["--current", cur, "--baseline", base]) == 1


class TestFanInAndPagedKeys:
    """Fan-in arbitration and paged-slot-cache keys (decode cells,
    serve.fanin_report): admission wait, eviction count, and the paged
    table's live-page HBM rent are all lower-is-better — the simulation
    is seeded, so any drift is a queue-discipline or paging change."""

    def test_all_new_keys_are_gated_lower(self):
        for m in ("fanin_admission_wait_s", "fanin_evictions",
                  "paged_hbm_bytes_per_slot"):
            assert bench_diff.METRICS[m] == "lower"

    def test_admission_wait_growth_fails(self):
        base = [_disagg_rec(fanin_admission_wait_s=0.010)]
        cur = [_disagg_rec(fanin_admission_wait_s=0.013)]   # +30%
        res = bench_diff.diff_trajectories(cur, base)
        assert [r["metric"] for r in res["regressions"]] \
            == ["fanin_admission_wait_s"]

    def test_eviction_thrash_and_paged_rent_growth_fail(self):
        base = [_disagg_rec(fanin_evictions=4.0,
                            paged_hbm_bytes_per_slot=10000)]
        cur = [_disagg_rec(fanin_evictions=6.0,                # +50%
                           paged_hbm_bytes_per_slot=13000)]    # +30%
        res = bench_diff.diff_trajectories(cur, base)
        assert sorted(r["metric"] for r in res["regressions"]) \
            == ["fanin_evictions", "paged_hbm_bytes_per_slot"]
        # fewer evictions / smaller rent never trips the gate
        res2 = bench_diff.diff_trajectories(
            [_disagg_rec(fanin_evictions=1.0,
                         paged_hbm_bytes_per_slot=6000)], base)
        assert res2["regressions"] == []

    def test_lost_paged_key_fails(self, tmp_path):
        """A paging change that stops emitting the HBM-per-slot key must
        fail the gate, not silently drop out of it."""
        base = _traj(tmp_path / "base.json",
                     [_disagg_rec(paged_hbm_bytes_per_slot=10000)])
        cur = _traj(tmp_path / "cur.json", [_disagg_rec()])
        assert bench_diff.main(["--current", cur, "--baseline", base]) == 1


class TestMainGate:
    def test_missing_baseline_tolerated(self, tmp_path):
        cur = _traj(tmp_path / "cur.json", [_rec()])
        assert bench_diff.main(["--current", cur,
                                "--baseline",
                                str(tmp_path / "nope.json")]) == 0

    def test_unreadable_baseline_tolerated(self, tmp_path):
        cur = _traj(tmp_path / "cur.json", [_rec()])
        bad = tmp_path / "bad.json"
        bad.write_text("not json{")
        assert bench_diff.main(["--current", cur,
                                "--baseline", str(bad)]) == 0

    def test_missing_current_fails(self, tmp_path):
        base = _traj(tmp_path / "base.json", [_rec()])
        assert bench_diff.main(["--current", str(tmp_path / "nope.json"),
                                "--baseline", base]) == 1

    def test_regression_exits_nonzero(self, tmp_path):
        base = _traj(tmp_path / "base.json", [_rec(collective_s=0.100)])
        cur = _traj(tmp_path / "cur.json", [_rec(collective_s=0.130)])
        assert bench_diff.main(["--current", cur, "--baseline", base]) == 1

    def test_green_trajectory_passes(self, tmp_path):
        recs = [_rec(collective_s=0.100, roofline_fraction=0.5),
                _rec(shape="decode_32k", grad_transport=None,
                     act_transport="int8", collective_s=0.031)]
        base = _traj(tmp_path / "base.json", recs)
        cur = _traj(tmp_path / "cur.json", json.loads(json.dumps(recs)))
        assert bench_diff.main(["--current", cur, "--baseline", base]) == 0
