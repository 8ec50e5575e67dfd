"""The ``serving_moe`` driver (Moonlight-16B-A3B's MLA + held-share expert
model) rehearsed on the CPU at a reduced size; its weights against the
reference's model; its work counts and per-layer metrics on hand-built
traces, each ``None`` without what it reads."""

import pytest

from bench import control
from bench.drivers import serving_moe
from bench.harness import core, peaks, work, work_moe
from bench.harness import trace as T
from bench.harness import weights as wlib
from bench.reference import mla_moe as moe_ref
from bench.tests.serving_moe_tiny import run, tiny

E = T.Event
V5E = peaks.peaks("TPU v5 lite")


def test_rehearsal_is_correct_and_counts_held_pairs():
    cfg, tr = tiny(requests=4, slots=4, max_new=6, check_requests=4)
    res = run(cfg, tr)
    assert res["correct"], res["checks"]
    assert res["attempted"] == 4 and res["failed"] == 0


@pytest.mark.parametrize("quant", control.SERVING_CONTROLS)
def test_lower_precision_controls_are_not_correct(quant):
    cfg, tr = tiny(requests=4, slots=4, max_new=6, check_requests=4)
    drv = core.load_module("drivers", "serving_moe").Driver
    res = run(cfg, tr, seed=5, driver_cls=control.lower_precision(drv, quant))
    assert not res["correct"], res["checks"]


def test_records_carry_the_counters():
    cfg, tr = tiny(requests=3, slots=3, max_new=4, check_requests=2)
    d = serving_moe.Driver(cfg, tr, 7, lambda s: None)
    d.setup()
    d.step()
    (r,) = d.records()
    pairs = r["decode_steps"] * 3 * cfg["num_experts_per_tok"] * (
        cfg["num_hidden_layers"] - cfg["first_k_dense_replace"])
    assert 0 < r["moe_held_pairs"] <= pairs
    assert 0 < r["moe_max_expert_tokens"] <= 3
    assert r["decode_steps"] == len(work.slot_schedule(4, 3, 3))


def test_program_weight_tree_is_the_reference_model():
    from repro.models import transformer
    cfg = core.load_json("configs", "moonlight-16b-a3b")
    abstract = transformer.abstract_params(serving_moe.program_config(cfg))
    assert wlib.shapes_of(abstract) == moe_ref.model_shapes(cfg)
    # the published widths: 64-wide router, 8 held experts, 27 layers
    shapes = moe_ref.model_shapes(cfg)
    assert shapes["moe/router"] == (26, 2048, 64)
    assert shapes["moe/w_gate"] == (26, 8, 2048, 1408)
    assert shapes["moe/shared/gate"] == (26, 2048, 2816)
    assert shapes["dense_mlp/gate"] == (1, 2048, 11264)
    assert shapes["layers/attn/wq"] == (27, 2048, 16, 192)
    assert shapes["lm_head"] == (2048, 163840)


@pytest.mark.parametrize("key,value", [
    ("scoring_func", "softmax"), ("n_group", 8), ("q_lora_rank", 1536),
    ("num_nextn_predict_layers", 1)])
def test_the_program_refuses_what_it_does_not_compute(key, value):
    cfg = core.load_json("configs", "moonlight-16b-a3b")
    with pytest.raises(ValueError, match=key):
        serving_moe.program_config(dict(cfg, **{key: value}))


# -- work counts -------------------------------------------------------------

MOON = core.load_json("configs", "moonlight-16b-a3b")


def test_work_counts_from_the_published_configuration():
    # one held expert: 3 x 2048 x 1408; 8 held in each of 26 layers
    assert work_moe.expert_params(MOON) == 8_650_752
    assert work_moe.held_expert_bytes(MOON) == 2 * 26 * 8 * 8_650_752
    # attention 13.76 M a layer, dense MLP 69.2 M, router + shared 17.4 M
    assert work_moe.attention_params(MOON) == 13_762_560
    assert work_moe.token_params(MOON) == (27 * 13_762_560 + 69_206_016
                                           + 26 * (131_072 + 17_301_504))
    # every weight once: 3.364 B parameters less the 335.5 M embedding
    assert work_moe.weight_bytes_read(MOON) == pytest.approx(
        2 * (3_364_615_296 - 163_840 * 2048), abs=0)
    assert work_moe.held_share(MOON) == 0.75


def test_experts_decode_counts():
    f, b = work_moe.experts_decode(MOON, held_pairs=96, steps=2)
    assert f == 2.0 * 8_650_752 * 96
    assert b == 2 * work_moe.held_expert_bytes(MOON) + 2 * 2 * 2048 * 96


def test_decode_step_counts():
    f, b = work_moe.decode_step(MOON, [300] * 128, held_pairs=96 * 26)
    # bytes: weights, 128 embedding rows, the live latent cache
    assert b == (work_moe.weight_bytes_read(MOON) + 2 * 2048 * 128
                 + 2 * 27 * 576 * 300 * 128)
    assert b / V5E["hbm_bytes_per_s"] == pytest.approx(8.856e-3, rel=1e-3)
    assert f == (2.0 * 128 * (work_moe.token_params(MOON) + 2048 * 163840)
                 + 2.0 * 8_650_752 * 96 * 26
                 + 2.0 * 27 * 16 * (2 * 512 + 64) * 300 * 128)


def test_generate_call_spreads_the_counted_pairs():
    call = work_moe.generate_call(MOON, [10, 20], max_new=3, n_slots=2,
                                  held_pairs=40)
    assert call["decode_steps"] == 2
    with_pairs = work_moe.decode_step(MOON, [11, 21], 20)
    assert call["decode"][0] == with_pairs
    assert call["model_flops"] > sum(
        work_moe.prefill_flops(MOON, n) for n in (10, 20))


# -- per-layer metrics on hand-built traces ---------------------------------

KERNEL = "%expert_gmm.3 = bf16[1024,2048] custom-call(%a)"


def serve_trace(with_kernel=True):
    """Two decode steps of 100 ns each; inside each, 40 ns of the grouped
    matmul and 50 ns of other operations; a prefill whose grouped matmul
    (10 ns) is not a decode step's."""
    host = [E("window", 0, 1000)]
    mods = [E("jit_prefill_step(1)", 50, 150), E("jit_decode_step(2)", 200,
                                                 300),
            E("jit_decode_step(2)", 400, 500)]
    ops = [E(KERNEL, 60, 70), E("%fusion.1 = f32[8] fusion(%b)", 80, 140)]
    for s in (200, 400):
        if with_kernel:
            ops.append(E(KERNEL, s + 10, s + 50))
        ops.append(E("%fusion.2 = bf16[8] fusion(%c)", s + 50, s + 100))
    return T.from_lines([host], [ops], [mods])


RECORD = {"lens": [10, 20], "max_new": 3, "slots": 2, "decode_steps": 2,
          "moe_held_pairs": 40, "moe_max_expert_tokens": 3}


def reduce(name, tr, records=(RECORD,), config=MOON):
    run_ = core.RunData(config=config, traffic={}, peak=V5E,
                        records=list(records), trace=tr)
    return core.load_module("metrics", name).reduce(run_)


def test_expert_roofline_reads_the_kernel_inside_decode_steps():
    got = reduce("moe_experts.roofline", serve_trace())
    least = work.roofline_s(*work_moe.experts_decode(MOON, 40, 2), V5E)
    assert got == pytest.approx(100.0 * least / 80e-9)
    assert reduce("moe_experts.roofline", serve_trace(False)) is None


def test_decode_step_roofline_and_mfu():
    call = work_moe.generate_call(MOON, [10, 20], 3, 2, 40)
    least = sum(work.roofline_s(f, b, V5E) for f, b in call["decode"])
    got = reduce("moe_decode_step.roofline", serve_trace())
    assert got == pytest.approx(100.0 * least / 200e-9)
    mfu = reduce("moe_serve.mfu", serve_trace())
    assert mfu == pytest.approx(100.0 * call["model_flops"] / 1000e-9
                                / V5E["bf16_flops_per_s"])


@pytest.mark.parametrize("name", ["moe_experts.roofline",
                                  "moe_decode_step.roofline",
                                  "moe_serve.mfu"])
def test_metrics_read_nothing_without_the_counters(name):
    """A program without the expert counters (or a run without records)
    gives no reading, and raises nothing."""
    bare = {k: v for k, v in RECORD.items() if not k.startswith("moe_")}
    assert reduce(name, serve_trace(), records=[bare]) is None
    assert reduce(name, serve_trace(), records=[]) is None
    assert reduce(name, None) is None
