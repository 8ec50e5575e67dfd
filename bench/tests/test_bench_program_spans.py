"""The per-layer metrics that read the program's own spans
(``repro.spans``), on small traces whose numbers are worked out by hand:
the shard merge's steps, the table's metadata writes and the slot
engine's per-step, admission and re-tracing idle time. Each returns
``None`` where its spans, or the device's operations, are missing."""

import pytest

from bench.harness import core
from bench.harness import trace as T

E = T.Event

COMPACT = ("merge_read_share.compact", "merge_copy_share.compact",
           "merge_encode_share.compact", "merge_device_wait_share.compact",
           "commit_metadata_share.compact")
SERVE = ("step_gap_ms.serve", "admit_idle_share.serve",
         "retrace_share.serve")
DEVICE = ("merge_device_wait_share.compact",) + SERVE


def reduce(name, tr):
    run = core.RunData(config={}, traffic={}, peak=None, records=[],
                       trace=tr)
    return core.load_module("metrics", name).reduce(run)


def compaction_trace(n_chips=1):
    # times in ns; the window runs 0..1000
    host = [E("window", 0, 1000), E("table.metadata", 10, 20),
            E("cycle", 90, 710), E("merge", 100, 700),
            E("merge.shards", 110, 690),
            E("merge.read", 110, 200), E("merge.concat", 200, 260),
            E("merge.device", 260, 460), E("merge.reslice", 460, 520),
            E("merge.encode", 520, 600), E("merge.store", 600, 690),
            E("ingest", 740, 1000), E("commit", 750, 800),
            E("table.commit", 750, 800), E("table.metadata", 770, 800)]
    # the device works only inside merge.device: 90 of its 200 ns
    ops = [E("%gather.1 = s32[8] custom-call(%a)", 300, 350),
           E("%copy.2 = s32[8] copy(%b)", 380, 420)]
    mods = [E("jit_compact_gather(3)", 290, 430)]
    return T.from_lines([host], [ops] * n_chips, [mods] * n_chips)


@pytest.mark.parametrize("n_chips", [1, 2])
def test_merge_and_commit_shares(n_chips):
    tr = compaction_trace(n_chips)
    got = {m: reduce(m, tr) for m in COMPACT}
    assert got == pytest.approx({
        "merge_read_share.compact": 9.0,          # 110-200
        "merge_copy_share.compact": 12.0,         # 200-260, 460-520
        "merge_encode_share.compact": 17.0,       # 520-600, 600-690
        "merge_device_wait_share.compact": 11.0,  # 200 - 90 busy
        "commit_metadata_share.compact": 4.0,     # 10-20, 770-800
    })


def test_merge_shares_add_up_to_the_merge_host_share():
    tr = compaction_trace()
    host = reduce("merge_host_share.compact", tr)
    parts = sum(reduce(m, tr) for m in COMPACT[:4])
    # the driver's merge span (100-700) holds merge.shards (110-690),
    # which its steps tile: 20 ns of the driver's bookkeeping are left
    assert host == pytest.approx(51.0)
    assert parts + 2.0 == pytest.approx(host)


def serving_trace(n_chips=1):
    # times in ns; the window and the driver's generate run 0..2000
    host = [E("window", 0, 2000), E("generate", 0, 2000),
            E("serve.generate", 10, 1990), E("serve.setup", 10, 100),
            E("lower_sharding_computation", 50, 90),
            E("serve.prefill", 100, 150),
            E("serve.admit", 150, 250), E("serve.transfer_wait", 150, 200),
            E("serve.prefill", 220, 250),
            # lowering outside serve.generate is not the engine's
            E("lower_sharding_computation", 1992, 1998)]
    ops = [E("%fusion.1 = bf16[8] fusion(%p)", 120, 150),     # prefill
           E("%fusion.2 = bf16[8] fusion(%a)", 160, 210)]     # admit
    mods = []
    for k in range(3):
        base = 300 + 400 * k
        host += [E("serve.decode", base, base + 40),
                 E("serve.sample", base + 40, base + 300),
                 E("serve.emit", base + 300, base + 340)]
        ops.append(E("%fusion.3 = bf16[8] fusion(%d)", base + 30,
                      base + 280))
        mods.append(E("jit_decode_step(5)", base + 30, base + 280))
    # the first step traces and lowers its new program
    host += [E("trace_to_jaxpr_dynamic", 300, 305),
             E("lower_sharding_computation", 305, 325)]
    return T.from_lines([host], [ops] * n_chips, [mods] * n_chips)


@pytest.mark.parametrize("n_chips", [1, 2])
def test_serving_idle_split(n_chips):
    tr = serving_trace(n_chips)
    # a step: idle 30 in decode, 20 in sample (280-300), 40 in emit; the
    # first step less its 25 ns of tracing and lowering
    assert reduce("step_gap_ms.serve", tr) == pytest.approx(
        1e3 * (65 + 90 + 90) / 3 * 1e-9)
    # prefill 100-150 idle 20; admit 150-250 idle 10 + 40
    assert reduce("admit_idle_share.serve", tr) == pytest.approx(
        100.0 * 70 / 2000)
    # set-up's lowering (40) and the first step's (25), not the one
    # outside serve.generate
    assert reduce("retrace_share.serve", tr) == pytest.approx(
        100.0 * 65 / 2000)


def test_serving_split_covers_the_idle_time_in_generate():
    tr = serving_trace()
    steps = 3
    covered = (reduce("step_gap_ms.serve", tr) * 1e-3 * steps
               + (reduce("admit_idle_share.serve", tr)
                  + reduce("retrace_share.serve", tr)) / 100
               * T.window_s(tr))
    # what is left: set-up outside lowering (10-50, 90-100), 250-300,
    # the gaps between steps, the end of the call
    idle_in_generate = T.idle_s_in(tr, "generate")
    assert covered < idle_in_generate
    assert covered == pytest.approx((245 + 70 + 65) * 1e-9)


def test_missing_spans_read_none():
    # a trace of a program without its own spans: the driver's only
    host = [E("window", 0, 1000), E("cycle", 0, 500), E("merge", 10, 400),
            E("generate", 500, 1000),
            E("lower_sharding_computation", 600, 650)]
    ops = [E("%fusion.1 = s32[8] fusion(%a)", 100, 200)]
    tr = T.from_lines([host], [ops], [[]])
    assert {m: reduce(m, tr) for m in COMPACT + SERVE} == \
        dict.fromkeys(COMPACT + SERVE)


def test_device_metrics_read_none_without_device_ops():
    # a CPU trace: the program's spans, no device plane
    for tr in (compaction_trace(), serving_trace()):
        cpu = T.Trace(tr.host, [], [])
        for m in DEVICE:
            assert reduce(m, cpu) is None, m
    cpu = T.Trace(compaction_trace().host, [], [])
    assert reduce("merge_read_share.compact", cpu) == pytest.approx(9.0)


def test_no_trace_reads_none():
    assert all(reduce(m, None) is None for m in COMPACT + SERVE)


def test_reducers_read_the_programs_span_names():
    from repro import spans
    defined = {v for k, v in vars(spans).items()
               if k.isupper() and isinstance(v, str)}
    for m in COMPACT + SERVE:
        mod = core.load_module("metrics", m)
        read = set()
        for k, v in vars(mod).items():
            if k.isupper() and k != "LOWERING" and \
                    isinstance(v, (str, tuple)):
                read |= {v} if isinstance(v, str) else set(v)
        assert read and read <= defined, (m, read - defined)
