"""The serving driver rehearsed on the CPU with a reduced MLA model, and
the agreement of the program's weight tree with the reference's model."""

import numpy as np
import pytest

from bench.drivers import serving
from bench.harness import core
from bench.harness import weights as wlib
from bench.reference import mla as mla_ref
from bench.tests.serving_tiny import run, tiny


@pytest.mark.parametrize("traffic,over", [
    ("decode", {"requests": 4, "slots": 4, "max_new": 6,
                "check_requests": 4}),
    # the prefill mix's shape: as many slots as requests, few new tokens
    ("prefill", {"check_requests": 3}),
    # fewer slots than requests: admissions wait for a free slot
    ("decode", {"requests": 5, "slots": 2, "max_new": 4,
                "check_requests": 5}),
])
def test_rehearsal_is_correct(traffic, over):
    cfg, tr = tiny(traffic, **over)
    res = run(cfg, tr)
    assert res["correct"], res["checks"]
    assert res["attempted"] == tr["requests"] and res["failed"] == 0
    m = res["metrics"]
    assert m["serve_tok_s"]["value"] > 0 and m["itl_ms"]["value"] > 0
    assert list(res)[-1] == "checks"


def test_decode_steps_follow_the_slot_schedule():
    from bench.harness import work
    cfg, tr = tiny("decode", requests=5, slots=2, max_new=4,
                   check_requests=2)
    d = serving.Driver(cfg, tr, 7, lambda s: None)
    d.setup()
    d.step()
    assert d.calls[0]["decode_steps"] == len(
        work.slot_schedule(tr["max_new"], tr["slots"], tr["requests"]))


def test_prompt_lengths_are_one_set_in_seeded_orders():
    cfg, tr = tiny("decode", requests=16, slots=16, max_new=2)
    a = serving.Driver(cfg, tr, 1, None)
    b = serving.Driver(cfg, tr, 2 ** 40 + 1, None)
    (pa, la), (pb, lb) = a.requests(0), b.requests(0)
    assert sorted(la) == sorted(lb) and list(la) != list(lb)
    assert (pa[np.arange(16)[None, :] >= la[:, None]] == 0).all()
    assert not np.array_equal(pa, pb)


# -- the program's weights are the reference's model -----------------------

def test_program_weight_tree_is_the_reference_model():
    from repro.models import transformer
    cfg = core.load_json("configs", "minicpm3-4b")
    abstract = transformer.abstract_params(serving.program_config(cfg))
    assert wlib.shapes_of(abstract) == mla_ref.model_shapes(cfg)


def test_layer_of_a_stacked_leaf_rebuilds_alone():
    import jax
    abstract = {"layers": {"mlp": {"up": jax.ShapeDtypeStruct(
        (3, 4, 5), np.float32)}}, "embed": jax.ShapeDtypeStruct(
        (6, 4), np.float32)}
    tree = wlib.build(2 ** 35 + 9, abstract)
    base = wlib.base_key(2 ** 35 + 9)
    for layer in range(3):
        one = wlib.layer_leaf(base, "layers/mlp/up", (4, 5), layer)
        assert np.array_equal(np.asarray(tree["layers"]["mlp"]["up"][layer]),
                              np.asarray(one))
    assert not np.array_equal(np.asarray(wlib.build(9, abstract)["embed"]),
                              np.asarray(tree["embed"]))


def test_the_program_cannot_run_a_scaling_it_lacks():
    cfg = core.load_json("configs", "minicpm3-4b")
    with pytest.raises(ValueError, match="scale_emb"):
        serving.program_config(dict(cfg, scale_emb=12))
