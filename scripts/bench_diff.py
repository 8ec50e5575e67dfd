#!/usr/bin/env python
"""Bench trajectory gate: diff a fresh ``BENCH_roofline.json`` against the
previous run's artifact and fail on performance regressions.

The CI ``bench-smoke`` job downloads the ``BENCH_roofline`` artifact from
the last successful main run and calls::

    python scripts/bench_diff.py --current BENCH_roofline.json \
        --baseline baseline/BENCH_roofline.json

Cells are matched by (arch, shape, mesh, preset, grad_transport,
act_transport). A cell regresses when a lower-is-better metric
(``collective_s``) grows, or a higher-is-better metric
(``roofline_fraction``, ``slot_stream_overlap_frac_*``) shrinks, by more
than ``--threshold`` (default 15%). A missing/unreadable baseline is
tolerated (first run, expired artifact): the gate passes with a note.
Cells present on only one side are reported but never fail the gate —
sweeps legitimately grow. A gated METRIC the baseline cell has but the
current cell lost, however, FAILS: a renamed roofline key must not
silently stop being gated.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Any, Dict, List, Optional, Tuple

# metric -> direction: "lower" means growth is a regression, "higher"
# means shrinkage is
METRICS: Dict[str, str] = {
    "collective_s": "lower",
    "roofline_fraction": "higher",
}

# Disaggregated-decode design space (decode cells only; a metric missing
# from BOTH records => skipped, so pre-disagg baselines stay comparable —
# but a metric the baseline HAS that the current record LOST fails the
# gate: a renamed roofline key must not silently stop being gated).
# The per-batch transfer and per-token decode-step components are gated
# individually: the combo sum is transfer-dominated, so a large
# decode-step regression would hide inside it. slot_stream_* are the
# continuous-streaming keys: per-slot wire bytes / transfer time (lower)
# and the double-buffer overlap efficiency (higher — the fraction of a
# slot transfer hidden behind decode steps). Note the overlap frac is a
# RATIO of two gated quantities (hide_steps * decode_step_s /
# slot_transfer_s), so a deliberate >threshold improvement in decode-step
# wire also shrinks it and trips this gate — by design: less decode time
# genuinely hides less transfer, and a PR that changes that trade-off
# must say so (and refresh the baseline by landing) rather than slip by.
_TRANSFERS = ("bf16", "int8")
_STORAGES = ("bf16", "int8", "f8")
for _t in _TRANSFERS:
    METRICS[f"disagg_transfer_s_{_t}"] = "lower"
    METRICS[f"slot_stream_transfer_s_{_t}"] = "lower"
    METRICS[f"slot_stream_wire_bytes_{_t}"] = "lower"
for _s in _STORAGES:
    METRICS[f"disagg_decode_step_s_{_s}"] = "lower"
for _t in _TRANSFERS:
    for _s in _STORAGES:
        METRICS[f"disagg_collective_s_{_t}x{_s}"] = "lower"
        METRICS[f"slot_stream_overlap_frac_{_t}x{_s}"] = "higher"
METRICS["disagg_tuned_collective_s"] = "lower"

# Fleet-scale compaction cells (arch "fleet-sim", benchmarks/bench_fleet.py).
# All lower-is-better: the simulated storm is seeded, so drift means a
# behavior change in the scheduler, not noise. p99 read latency and final
# file count are the user-facing outcomes; gbhr_total bounds compute burn
# under the shared budget; starvation_max_cycles gates the aging invariant
# (a scheduler change that lets fragmented tables wait longer must fail).
for _m in ("fleet_p99_query_s", "fleet_file_count_final",
           "fleet_gbhr_total", "fleet_starvation_max_cycles"):
    METRICS[_m] = "lower"
# Retention cells (shape suffix "_ret", bench_fleet.py --retention):
# rows_dropped is higher-is-better — a scheduler/pricing change that
# starves delete candidates shows up as fewer rows deleted under the same
# budget and must fail; retention_bytes_rewritten is lower-is-better —
# boundary-aligned deletes must stay tier-1 metadata drops, so a router
# change that demotes them to rewrites burns bytes and trips this gate.
METRICS["fleet_rows_dropped"] = "higher"
METRICS["fleet_retention_bytes_rewritten"] = "lower"

# Tunable-kernel cells (arch "kernel", benchmarks/bench_kernels.py --json).
# kernel_<op>_tuned_s is the trajectory the sweep harness must keep
# monotone: serving always reads the tuned point from the persisted cache,
# so a regression here means either the sweep picked a worse point or the
# kernel itself got slower. The filter cells gate the fused filter+pack
# hot path: its step time AND its analytic HBM traffic (plan-derived, so
# deterministic — a plan change that re-reads dropped rows fails even if
# the stopwatch is noisy).
for _op in ("compact_pack", "flash_attn", "decode_attn", "paged_attn",
            "rmsnorm", "expert_a2a", "expert_gmm"):
    METRICS[f"kernel_{_op}_tuned_s"] = "lower"
METRICS["kernel_compact_filter_s"] = "lower"
METRICS["kernel_compact_filter_hbm_bytes"] = "lower"

# Fan-in arbitration keys (decode cells, serve.fanin_report — a
# deterministic simulation driving the real AdmissionArbiter, so drift is
# a queue-discipline change, not noise). fanin_admission_wait_s is the
# mean per-admission latency (queue wait + unhidden transfer);
# fanin_evictions counts preemptions the policy performed (each costs a
# re-prefill of the extended prompt, so an arbiter change that thrashes
# the slot table must fail); paged_hbm_bytes_per_slot is the paged slot
# cache's live-page resident rent — the saving over the dense
# pad-to-horizon layout the paged table exists to buy, gated so a paging
# change cannot silently give it back.
for _m in ("fanin_admission_wait_s", "fanin_evictions",
           "paged_hbm_bytes_per_slot"):
    METRICS[_m] = "lower"

DEFAULT_THRESHOLD = 0.15


def cell_key(rec: Dict[str, Any]) -> Tuple:
    # every field that names a distinct dry-run variant must participate,
    # or variant cells silently collide and diff against the wrong baseline
    return (rec.get("arch"), rec.get("shape"), rec.get("mesh"),
            rec.get("preset"), rec.get("grad_transport"),
            rec.get("act_transport"), rec.get("microbatches"),
            rec.get("remat_block"), rec.get("capacity_factor"))


def _ok_cells(records: List[Dict[str, Any]]) -> Dict[Tuple, Dict[str, Any]]:
    return {cell_key(r): r for r in records
            if r.get("status") == "ok" and isinstance(r.get("roofline"), dict)}


def diff_trajectories(current: List[Dict[str, Any]],
                      baseline: List[Dict[str, Any]],
                      threshold: float = DEFAULT_THRESHOLD,
                      metrics: Optional[Dict[str, str]] = None
                      ) -> Dict[str, Any]:
    """Compare two record lists; returns {regressions, missing_metrics,
    compared, only_*}.

    Each regression is ``{key, metric, baseline, current, change}`` with
    ``change`` the signed relative move in the bad direction (e.g. +0.30
    for a 30% collective_s growth). ``missing_metrics`` lists gated
    metrics the baseline cell HAS but the current cell LOST — a renamed
    or dropped roofline key must fail loudly, not silently stop being
    gated (metrics absent from both sides stay skipped, so old baselines
    remain comparable as the key set grows).
    """
    metrics = METRICS if metrics is None else metrics
    cur = _ok_cells(current)
    base = _ok_cells(baseline)
    regressions: List[Dict[str, Any]] = []
    missing: List[Dict[str, Any]] = []
    compared = 0
    for key, crec in cur.items():
        brec = base.get(key)
        if brec is None:
            continue
        compared += 1
        for metric, direction in metrics.items():
            cval = crec["roofline"].get(metric)
            bval = brec["roofline"].get(metric)
            if not isinstance(bval, (int, float)):
                continue
            if not isinstance(cval, (int, float)):
                missing.append({"key": key, "metric": metric,
                                "baseline": bval})
                continue
            if bval == 0:
                continue
            rel = (cval - bval) / abs(bval)
            bad = rel if direction == "lower" else -rel
            if bad > threshold:
                regressions.append({
                    "key": key, "metric": metric,
                    "baseline": bval, "current": cval,
                    "change": round(bad, 4),
                })
    return {
        "regressions": regressions,
        "missing_metrics": missing,
        "compared": compared,
        "only_current": sorted(str(k) for k in cur.keys() - base.keys()),
        "only_baseline": sorted(str(k) for k in base.keys() - cur.keys()),
    }


def load_records(path: str) -> Optional[List[Dict[str, Any]]]:
    """Records list from a BENCH_roofline.json payload; None if unusable."""
    if not os.path.exists(path):
        return None
    try:
        with open(path) as f:
            payload = json.load(f)
        recs = payload.get("records") if isinstance(payload, dict) else None
        return recs if isinstance(recs, list) else None
    except (OSError, ValueError):
        return None


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--current", required=True,
                    help="fresh BENCH_roofline.json")
    ap.add_argument("--baseline", required=True,
                    help="previous run's BENCH_roofline.json "
                         "(missing => tolerated, gate passes)")
    ap.add_argument("--threshold", type=float, default=DEFAULT_THRESHOLD,
                    help="relative regression tolerance (default 0.15)")
    args = ap.parse_args(argv)

    current = load_records(args.current)
    if current is None:
        print(f"[bench-diff] FAIL: current trajectory {args.current!r} "
              "missing or unreadable")
        return 1
    baseline = load_records(args.baseline)
    if baseline is None:
        print(f"[bench-diff] no usable baseline at {args.baseline!r} "
              "(first run or expired artifact) — gate passes")
        return 0

    res = diff_trajectories(current, baseline, threshold=args.threshold)
    print(f"[bench-diff] compared {res['compared']} cells "
          f"(threshold {args.threshold:.0%}); "
          f"{len(res['only_current'])} new, "
          f"{len(res['only_baseline'])} baseline-only")
    for k in res["only_current"]:
        print(f"  new cell (not gated): {k}")
    for k in res["only_baseline"]:
        print(f"  dropped cell (not gated): {k}")
    for m in res["missing_metrics"]:
        print(f"  MISSING {m['key']}: gated metric {m['metric']!r} "
              f"(baseline {m['baseline']:.6g}) disappeared from the fresh "
              "artifact — renamed keys must not silently stop being gated")
    if not res["regressions"] and not res["missing_metrics"]:
        print("[bench-diff] OK: no regression beyond threshold")
        return 0
    for r in res["regressions"]:
        print(f"  REGRESSION {r['key']}: {r['metric']} "
              f"{r['baseline']:.6g} -> {r['current']:.6g} "
              f"({r['change']:+.1%} in the bad direction)")
    print(f"[bench-diff] FAIL: {len(res['regressions'])} regression(s), "
          f"{len(res['missing_metrics'])} disappeared metric(s)")
    return 1


if __name__ == "__main__":
    sys.exit(main())
