"""The trace reduction on small traces whose numbers are worked out by
hand: busy union, idle share, device time per program, time inside host
spans, gaps named by the host span around them, and ``breakdown``."""

import pytest

from bench.harness import trace as T

E = T.Event


def small_trace(n_chips=1):
    # times in ns; the window runs 0..1000
    host = [E("window", 0, 1000), E("cycle", 0, 600), E("merge", 100, 400),
            E("PjitFunction(_run)", 150, 160), E("merge", 450, 550),
            E("generate", 700, 1000)]
    ops = [E("%fusion.1 = s32[8] fusion(%a)", 120, 200),
           E("%fusion.1 = s32[8] fusion(%a)", 180, 250),      # overlap
           E("%copy.2 = s32[8] copy(%b)", 300, 350),
           E("%while.5 = (s32[]) while(%t)", 500, 520),       # a loop ...
           E("%fusion.3 = f32[2] fusion(%c)", 501, 510),      # ... its body
           E("%fusion.3 = f32[2] fusion(%c)", 511, 519),
           E("%fusion.4 = f32[2] fusion(%d)", 990, 1100)]     # past the end
    mods = [E("jit__run(11)", 110, 360), E("jit_decode_step(7)", 480, 530),
            E("jit_decode_step(7)", 980, 1200)]
    return T.from_lines([[E("other thread", 0, 5)], host],
                        [ops] * n_chips, [mods] * n_chips)


def test_union_and_interval_arithmetic():
    assert T.union([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)]) == \
        [(0, 3), (5, 8)]
    assert T.intersect([(0, 3), (5, 8)], [(2, 6)]) == [(2, 3), (5, 6)]
    assert T.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert T.clip([(-5, 2), (8, 20)], 0, 10) == [(0, 2), (8, 10)]


def test_busy_and_idle_share():
    tr = small_trace()
    # union: 120-250 (130), 300-350 (50), 500-520 (20), 990-1000 (10)
    assert tr.window == (0, 1000)
    assert T.busy(tr)[0] == [(120, 250), (300, 350), (500, 520), (990, 1000)]
    assert T.busy_s(tr) == pytest.approx(210e-9)
    assert T.window_s(tr) == pytest.approx(1000e-9)
    assert T.idle_share(tr) == pytest.approx(1 - 0.21)


def test_mean_over_chips():
    tr = small_trace(n_chips=2)
    assert T.busy_s(tr) == pytest.approx(210e-9)


def test_spans_and_device_time_inside_them():
    tr = small_trace()
    assert T.spans(tr, "merge") == [(100, 400), (450, 550)]
    # busy inside merge: 120-250, 300-350, 500-520
    assert T.device_s_in(tr, "merge") == pytest.approx(200e-9)
    assert T.idle_s_in(tr, "merge") == pytest.approx(200e-9)


def test_program_time_by_name():
    tr = small_trace()
    assert T.module_name("jit_decode_step(7)") == "decode_step"
    assert T.module_name("jit__run(11)") == "_run"
    secs, n = T.module_s(tr, "decode_step")
    assert n == 2 and secs == pytest.approx((50 + 20) * 1e-9)
    assert T.module_s(tr, "prefill_step") == (0.0, 0)


def test_breakdown_names_ops_and_gaps():
    tr = small_trace()
    b = T.breakdown(tr, top=3)
    # durations per program:op, clipped to the window
    assert [k for k, _ in b["device_ops"]] == [
        "_run:fusion.1", "_run:copy.2", "decode_step:fusion.3"]
    assert [s for _, s in b["device_ops"]] == pytest.approx(
        [150e-9, 50e-9, 17e-9])
    # idle gaps of chip 0: 0-120, 250-300, 350-500, 520-990
    names = [name for name, _ in b["idle_gaps"]]
    secs = [s for _, s in b["idle_gaps"]]
    assert secs == pytest.approx([470e-9, 150e-9, 120e-9])
    # 520-990: middle 755, inside generate (700-1000) only
    # 350-500: middle 425, inside cycle only (merges end at 400, start 450)
    # 0-120: middle 60, inside cycle
    assert names == ["generate", "cycle", "cycle"]


def test_op_names_and_loop_bodies():
    assert T.op_name("%fusion.195 = bf16[64] fusion(%x), kind=kLoop") == \
        "fusion.195"
    ops = small_trace().ops[0]
    names = [T.op_name(e.name) for e in T.leaf_ops(ops)]
    assert "while.5" not in names and names.count("fusion.3") == 2


def test_one_window_span_required():
    with pytest.raises(ValueError):
        T.from_lines([[E("merge", 0, 1)]], [], [])


def recorded():
    """70 ms of a traced ``generate`` call on a TPU v5e (prefills and
    admissions), with the trace's own names, op names cut to the HLO
    instruction."""
    import json
    import pathlib
    with open(pathlib.Path(__file__).parent / "data" /
              "v5e_serve_excerpt.json") as f:
        d = json.load(f)

    def ev(rows):
        return [E(*r) for r in rows]
    return T.from_lines([ev(d["host"])], [ev(d["ops"])], [ev(d["modules"])])


def test_recorded_trace_busy_matches_a_timeline():
    import numpy as np
    tr = recorded()
    lo, hi = tr.window
    step = 100                                     # ns per timeline bin
    line = np.zeros(int((hi - lo) // step) + 1, bool)
    for e in tr.ops[0]:
        a, b = max(e.start, lo), min(e.end, hi)
        if b > a:
            line[int((a - lo) // step):int(-(-(b - lo) // step))] = True
    assert T.busy_s(tr) == pytest.approx(line.sum() * step * 1e-9, rel=1e-2)
    assert 0 < T.idle_share(tr) < 1


def test_recorded_trace_programs_and_breakdown():
    tr = recorded()
    # two whole prefills of 15.68 ms and the first 2.8 ms of a third
    secs, n = T.module_s(tr, "prefill_step")
    assert n == 3 and secs == pytest.approx(2 * 15.68e-3 + 2.8e-3, rel=1e-2)
    assert T.module_s(tr, "admit")[1] >= 1
    b = T.breakdown(tr)
    assert len(b["device_ops"]) == 10
    assert all(k.split(":")[0] in {"prefill_step", "admit", "_lambda",
                                   "_argmax", "convert_element_type",
                                   "_threefry_fold_in", "?"}
               for k, _ in b["device_ops"])
    assert not any("while" in k for k, _ in b["device_ops"])
    assert sum(s for _, s in b["idle_gaps"]) <= T.window_s(tr)
    assert all(isinstance(name, str) for name, _ in b["idle_gaps"])
