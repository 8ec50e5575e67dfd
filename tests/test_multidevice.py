"""Real multi-device mesh validation — the suite the CI ``multidevice`` job
runs under ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.

Everything here executes on a non-trivial ``(data=4, model=2)`` mesh built
from 8 actual (forced-host) devices: resolved shardings are read back from
committed arrays, collective HLO is parsed from compiled programs, and the
int8_ef gradient transport is shown to move *fewer cross-pod collective
bytes* than the bf16 baseline — not just to simulate its rounding. Skipped
when fewer than 8 devices exist (the plain tier-1 job)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import smoke_config
from repro.dist import sharding as shd
from repro.launch import analysis
from repro.models import transformer
from repro.train import optimizer as opt_lib
from repro.train import step as step_lib

pytestmark = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")

DATA, MODEL = 4, 2


@pytest.fixture(scope="module")
def mesh():
    return jax.make_mesh((DATA, MODEL), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


@pytest.fixture(scope="module")
def cfg():
    return smoke_config("paper-lm-100m")


def _batch(cfg, batch=8, seq=32, seed=0):
    key = jax.random.PRNGKey(seed)
    toks = jax.random.randint(key, (batch, seq), 0, cfg.vocab, jnp.int32)
    labs = jax.random.randint(key, (batch, seq), 0, cfg.vocab, jnp.int32)
    return {"tokens": toks, "labels": labs}


class TestResolvedShardings:
    def test_param_shardings_on_real_mesh(self, mesh, cfg):
        """FSDP embed dim over data, tensor dims over model — read back from
        the committed arrays, not just the resolver."""
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        shards = shd.tree_shardings(transformer.abstract_params(cfg),
                                    transformer.param_axes(cfg), mesh)
        placed = jax.device_put(params, shards)
        # tied embedding (vocab, d): vocab -> model, embed -> data
        emb = placed["embed"]
        assert emb.sharding.spec == P("model", "data")
        local = emb.addressable_shards[0].data
        assert local.shape == (cfg.vocab // MODEL, cfg.d_model // DATA)
        # mlp gate (d, d_ff): embed -> data, mlp -> model
        gate = placed["layers"]["mlp"]["gate"]
        assert gate.sharding.spec[-2:] == ("data", "model")

    def test_constrain_places_activations(self, mesh):
        x = jnp.ones((8, 64))
        with shd.axis_rules(mesh):
            y = jax.jit(lambda t: shd.constrain(t, "batch", "mlp"))(x)
        assert y.sharding.spec == P("data", "model")


def _spmd_train_artifacts(cfg, mesh, grad_transport, rules=None):
    """jit the SPMD train step with explicit shardings and compile it."""
    rules = shd.PRESETS["baseline"] if rules is None else rules
    ef = grad_transport == "int8_ef"
    p_abs = transformer.abstract_params(cfg)
    p_axes = transformer.param_axes(cfg)
    p_shard = shd.tree_shardings(p_abs, p_axes, mesh, rules)
    o_abs = opt_lib.abstract_state(p_abs, error_feedback=ef)
    o_axes = opt_lib.state_axes(p_axes, error_feedback=ef)
    o_shard = shd.tree_shardings(o_abs, o_axes, mesh, rules)
    batch = _batch(cfg)
    b_shard = {k: NamedSharding(mesh, P("data")) for k in batch}
    fn = step_lib.make_train_step(cfg, opt_lib.AdamWConfig(),
                                  grad_transport=grad_transport)
    jfn = jax.jit(fn, in_shardings=(p_shard, o_shard, b_shard),
                  out_shardings=(p_shard, o_shard, None))
    b_abs = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
             for k, v in batch.items()}
    with shd.axis_rules(mesh, rules):
        compiled = jfn.lower(p_abs, o_abs, b_abs).compile()
    return compiled


class TestSpmdCollectiveHlo:
    def test_train_step_emits_grad_psum_and_weight_gather(self, mesh, cfg):
        """On the real (4,2) mesh the compiled SPMD step must reduce
        gradients (all-reduce/reduce-scatter) and gather FSDP weight shards
        (all-gather) — the 1x1 local mesh never exercises either."""
        compiled = _spmd_train_artifacts(cfg, mesh, "bf16")
        coll = analysis.hlo_collective_bytes(compiled.as_text())
        psum = coll["all-reduce"]["count"] + coll["reduce-scatter"]["count"]
        assert psum > 0
        assert coll["all-gather"]["count"] > 0
        assert coll["total_wire_bytes"] > 0

    def test_int8_ef_spmd_step_compiles_with_ef_state(self, mesh, cfg):
        compiled = _spmd_train_artifacts(cfg, mesh, "int8_ef")
        coll = analysis.hlo_collective_bytes(compiled.as_text())
        assert (coll["all-reduce"]["count"]
                + coll["reduce-scatter"]["count"]) > 0


def _dp_step_artifacts(cfg, mesh, grad_transport):
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    opt = opt_lib.init_state(params,
                             error_feedback=grad_transport == "int8_ef",
                             ef_devices=DATA)
    adamw = opt_lib.AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=10)
    step = jax.jit(step_lib.make_train_step(
        cfg, adamw, grad_transport=grad_transport, mesh=mesh))
    batch = _batch(cfg)
    compiled = step.lower(params, opt, batch).compile()
    return step, params, opt, batch, compiled


class TestInt8TransportOnTheWire:
    """The acceptance gate: the compiled int8_ef step moves fewer cross-pod
    collective bytes than the bf16 baseline on the (data=4, model=2) mesh
    (the data axis plays the cross-pod/DCI role)."""

    @pytest.fixture(scope="class")
    def artifacts(self, mesh, cfg):
        return {t: _dp_step_artifacts(cfg, mesh, t)
                for t in ("bf16", "int8_ef")}

    def test_bf16_baseline_reduces_per_leaf(self, artifacts):
        """One gradient all-reduce operand per parameter leaf (the CPU
        backend promotes the bf16 payload to f32 on the wire — that is
        exactly the promotion the *_bf16eq accounting compensates for).
        XLA's all-reduce combiner may fuse the leaves into one tuple
        all-reduce, so count the reduced arrays, not the instructions."""
        import re
        hlo = artifacts["bf16"][-1].as_text()
        ar_types = [l.split(" = ", 1)[1].split("all-reduce(", 1)[0]
                    for l in hlo.splitlines()
                    if "all-reduce(" in l and " = " in l]
        assert any("bf16[" in t or "f32[" in t for t in ar_types)
        n_reduced = sum(len(re.findall(r"\b(?:bf16|f32)\[", t))
                        for t in ar_types)
        n_param_leaves = len(jax.tree.leaves(artifacts["bf16"][1]))
        assert n_reduced >= n_param_leaves

    def test_int8_step_moves_int8_payloads(self, artifacts):
        hlo = artifacts["int8_ef"][-1].as_text()
        exch = [l for l in hlo.splitlines()
                if ("all-to-all(" in l or "all-gather(" in l) and " = " in l]
        assert any("s8[" in l for l in exch), \
            "int8 exchange must put s8 payloads on the wire"

    def test_int8_moves_fewer_bytes_than_bf16(self, artifacts):
        coll = {t: analysis.hlo_collective_bytes(a[-1].as_text())
                for t, a in artifacts.items()}
        for key in ("total_wire_bytes", "total_bytes",
                    "total_wire_bytes_bf16eq"):
            int8, bf16 = coll["int8_ef"][key], coll["bf16"][key]
            assert int8 < bf16, (key, int8, bf16)
        # by a margin in the right ballpark even after normalizing away the
        # CPU backend's bf16->f32 promotion: >= 1.5x on the wire
        assert coll["int8_ef"]["total_wire_bytes_bf16eq"] \
            <= coll["bf16"]["total_wire_bytes_bf16eq"] / 1.5

    def test_both_transports_train_to_similar_loss(self, artifacts):
        finals = {}
        for t, (step, params, opt, batch, _) in artifacts.items():
            p, o = params, opt
            for _ in range(6):
                p, o, m = step(p, o, batch)
            finals[t] = float(m["loss"])
            assert np.isfinite(finals[t])
        assert abs(finals["int8_ef"] - finals["bf16"]) \
            <= 0.05 * abs(finals["bf16"]), finals

    def test_ef_residual_is_per_device(self, artifacts, cfg):
        step, params, opt, batch, _ = artifacts["int8_ef"]
        _, o, _ = step(params, opt, batch)
        leaf = jax.tree.leaves(o["ef"])[0]
        assert leaf.shape[0] == DATA          # one residual per data shard
        per_dev = np.asarray(leaf).reshape(DATA, -1)
        norms = np.abs(per_dev).sum(axis=1)
        assert (norms > 0).all()              # every device carries error
