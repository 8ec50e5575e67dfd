"""The faults that must make the serving cell's ``correct`` false, and
the control's reading, on the CPU with a reduced MLA model."""

import pytest

from bench.control import lower_precision
from bench.drivers import serving
from bench.tests.serving_tiny import LIMITS, run, tiny


# -- faults: each must make ``correct`` false ------------------------------

def _patch_decode(monkeypatch, wrap):
    from repro.train import step as step_lib
    orig = step_lib.make_decode_step

    def make(*a, **kw):
        inner = orig(*a, **kw)

        def decode_step(params, cache, batch):
            return wrap(inner, params, cache, batch)
        return decode_step
    monkeypatch.setattr(step_lib, "make_decode_step", make)


def _alter_token(monkeypatch):
    """Slot 0's token shifted to the next id where it is chosen."""
    import jax.numpy as jnp

    def wrap(inner, params, cache, batch):
        logits, new = inner(params, cache, batch)
        return logits.at[0].set(jnp.roll(logits[0], 1)), new
    _patch_decode(monkeypatch, wrap)


def _state_unchanged(monkeypatch):
    """The decode step hands back the cache it was given."""
    def wrap(inner, params, cache, batch):
        logits, _ = inner(params, cache, batch)
        return logits, cache
    _patch_decode(monkeypatch, wrap)


def _half_the_batch(monkeypatch):
    """Only the first half of the slots computed; the rest copy them."""
    import jax.numpy as jnp

    def wrap(inner, params, cache, batch):
        logits, new = inner(params, cache, batch)
        h = logits.shape[0] // 2
        return jnp.concatenate([logits[:h], logits[:logits.shape[0] - h]]), \
            new
    _patch_decode(monkeypatch, wrap)


@pytest.mark.parametrize("fault", [_alter_token, _state_unchanged,
                                   _half_the_batch])
def test_fault_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    cfg, tr = tiny("decode", requests=4, slots=4, max_new=6,
                   check_requests=4)
    res = run(cfg, tr)
    assert not res["correct"], res["checks"]


def test_control_reads_above_the_limit():
    """The reference put in the program's place and computed in fp8 comes
    out not correct through the run's own check, on both numbers, where
    the program comes out correct (the readings at the cell's size, on
    the chip, with the int8 control: PERF.md)."""
    cfg, tr = tiny("decode", requests=8, slots=8, max_new=16,
                   check_requests=8)
    sound = run(cfg, tr, seed=3)
    control = run(cfg, tr, seed=3,
                  driver_cls=lower_precision(serving.Driver, "fp8"))
    assert sound["correct"], sound["checks"]
    assert not control["correct"]
    for name in serving.COMPARED:
        assert sound["checks"][name]["value"] <= LIMITS[name]
        assert control["checks"][name]["value"] > LIMITS[name]
