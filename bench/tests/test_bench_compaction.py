"""The compaction driver rehearsed on the CPU at a tiny size (interpreted
kernels), the faults and the control that must make ``correct`` false,
and the command's refusal to run without a chip."""

import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

from bench import ROOT
from bench.control import row_swap
from bench.drivers import compaction
from bench.harness import core

TINY = {"files": 24, "file_bytes": [4096, 40000],
        "write.target-file-size-bytes": 1 << 17}
E2E = [{"name": "rewrite_gbps", "unit": "GB/s"},
       {"name": "setup_s", "unit": "s"}]
PER_LAYER = [{"name": n, "unit": "%"} for n in (
    "control_share.compact", "ingest_share.compact",
    "merge_host_share.compact",
    "compact_pack.roofline", "compact_filter.roofline",
    "idle_share.compact")]


def tiny_config():
    cfg = core.load_json("configs", "tokenshard-iceberg")
    cfg.update(TINY)
    return cfg


def run(traffic="binpack", trace=False, seed=2 ** 31 + 11):
    """One iteration (``seconds=0``) through the whole run."""
    return core.run_cell({"name": "compact.tiny", "chips": 1},
                         tiny_config(), core.load_json("traffic", traffic),
                         E2E, PER_LAYER, {}, seed, 0.0, trace,
                         time.perf_counter(), require_chip=False,
                         log=lambda s: None)


@pytest.mark.parametrize("traffic", ["binpack", "delete30"])
def test_rehearsal_is_correct(traffic):
    res = run(traffic)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert res["attempted"] >= 2 and res["failed"] == 0
    assert res["metrics"]["rewrite_gbps"]["value"] > 0
    assert res["metrics"]["setup_s"]["unit"] == "s"
    assert res["device"]["platform"] == "cpu"


def test_delete_drops_about_the_fraction():
    drop = compaction.ref.drop_by_row_hash(0.3, 2654435769)
    rows = np.random.default_rng(0).integers(0, 32000, (20000, 128),
                                             dtype=np.int32)
    assert abs(drop(rows).mean() - 0.3) < 0.02


def test_traced_run_on_cpu_reads_no_device_metric():
    res = run(trace=True)
    assert res["correct"]
    # host spans only: a CPU trace has no device plane to read
    assert set(res["metrics"]) == {"control_share.compact",
                                   "ingest_share.compact"}
    for name in res["metrics"]:
        assert 0 < res["metrics"][name]["value"] < 100
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert res["device"]["window_s"] > 0


def test_every_seed_has_the_same_file_sizes():
    cfg = tiny_config()
    a, _ = compaction.make_pool(cfg, 1)
    b, _ = compaction.make_pool(cfg, 2 ** 40 + 1)
    assert sorted(t.size for t in a) == sorted(t.size for t in b)
    assert [t.size for t in a] != [t.size for t in b]


# -- faults and the control: each must make ``correct`` false -------------

def _alter_token(monkeypatch):
    from repro.data import packing
    orig = packing.compact_chunks

    def altered(*a, **kw):
        out = orig(*a, **kw)
        return out.at[0].add(1)
    monkeypatch.setattr(packing, "compact_chunks", altered)


def _half_of_the_inputs(monkeypatch):
    import dataclasses

    import repro.data
    orig = repro.data.merge_shards_fn

    def half(table, task, out_path, **kw):
        keep = task.inputs[:max(1, len(task.inputs) // 2)]
        return orig(table, dataclasses.replace(task, inputs=keep), out_path,
                    **kw)
    monkeypatch.setattr(repro.data, "merge_shards_fn", half)


def _state_unchanged(monkeypatch):
    from repro.core.act import ActReport, Scheduler
    monkeypatch.setattr(Scheduler, "execute", lambda self, sel: ActReport())


@pytest.mark.parametrize("fault", [_alter_token, _half_of_the_inputs,
                                   _state_unchanged])
def test_fault_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    res = run()
    assert not res["correct"]
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


def test_control_is_not_correct():
    with row_swap():
        res = run()
    assert not res["correct"]
    assert res["checks"]["contents_wrong"]["value"] > 0


# -- the command ---------------------------------------------------------------

def _command(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "compact.binpack",
         "--seed", str(2 ** 33), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_exits_nonzero_without_a_tpu():
    p = _command(ROOT, {})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_command_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _command(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "src/" in p.stderr
