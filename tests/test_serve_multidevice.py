"""Sharded serving on a real multi-device mesh — the serve-side mirror of
tests/test_multidevice.py, run by the CI ``multidevice`` job under
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.

On a forced (data=4, model=2) mesh with the ``serve_sp`` preset: the KV
cache's resolved sharding is data (batch) x model (sequence), the compiled
decode step all-gathers the sequence-sharded cache, and the
``act_transport="int8"`` program moves that gather as s8 chunks + f32
scales — < 1/1.5 the bf16 program's all-gather wire bytes — while greedy
decode stays token-for-token identical to bf16. Skipped below 8 devices
(the plain tier-1 job)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import smoke_config
from repro.dist import sharding as shd
from repro.launch import analysis
from repro.launch.serve import generate
from repro.models import transformer
from repro.train import step as step_lib

pytestmark = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")

DATA, MODEL = 4, 2
BATCH, TOTAL = 8, 512        # decode horizon: cache gather dominates wire


@pytest.fixture(scope="module")
def mesh():
    return jax.make_mesh((DATA, MODEL), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


@pytest.fixture(scope="module")
def cfg():
    return smoke_config("paper-lm-100m")


RULES = shd.PRESETS["serve_sp"]


class TestServeShardings:
    def test_cache_sharded_over_data_x_sequence(self, mesh, cfg):
        """serve_sp: batch dim -> data, kv_seq dim -> model — read back
        from committed arrays, not just the resolver."""
        cache = transformer.init_cache(cfg, BATCH, TOTAL)
        shards = shd.tree_shardings(
            transformer.abstract_cache(cfg, BATCH, TOTAL),
            transformer.cache_axes(cfg, BATCH, TOTAL), mesh, RULES)
        placed = jax.device_put(cache, shards)
        for name in ("k", "v"):
            leaf = placed[name]      # (layers, B, S, Hkv, hd)
            assert leaf.sharding.spec == P(None, "data", "model")
            local = leaf.addressable_shards[0].data
            assert local.shape == (cfg.n_layers, BATCH // DATA,
                                   TOTAL // MODEL, cfg.n_kv_heads,
                                   cfg.head_dim)

    def test_weights_replicated_over_data(self, mesh, cfg):
        """Serving drops the FSDP embed shard: weights are read-only and
        resident, so no per-token regather dilutes the wire."""
        p_shard = shd.tree_shardings(transformer.abstract_params(cfg),
                                     transformer.param_axes(cfg), mesh, RULES)
        gate_spec = p_shard["layers"]["mlp"]["gate"].spec
        assert "data" not in jax.tree.leaves(tuple(gate_spec))
        assert "model" in jax.tree.leaves(tuple(gate_spec))


def _decode_artifacts(cfg, mesh, act_transport):
    """Compile the serve decode step with explicit serve_sp shardings."""
    p_abs = transformer.abstract_params(cfg)
    p_shard = shd.tree_shardings(p_abs, transformer.param_axes(cfg),
                                 mesh, RULES)
    c_abs = transformer.abstract_cache(cfg, BATCH, TOTAL)
    c_shard = shd.tree_shardings(
        c_abs, transformer.cache_axes(cfg, BATCH, TOTAL), mesh, RULES)
    batch = {"tokens": jax.ShapeDtypeStruct((BATCH, 1), jnp.int32),
             "pos": jax.ShapeDtypeStruct((), jnp.int32)}
    fn = step_lib.make_decode_step(cfg, TOTAL, act_transport)
    jfn = jax.jit(fn, in_shardings=(p_shard, c_shard, None),
                  out_shardings=(None, c_shard))
    with shd.axis_rules(mesh, RULES):
        return jfn.lower(p_abs, c_abs, batch).compile()


class TestInt8ActivationCollectives:
    """The acceptance gate: decode's cache all-gather moves s8 on the wire
    and < 1/1.5 the bf16 bytes, HLO-verified on the (4, 2) mesh."""

    @pytest.fixture(scope="class")
    def artifacts(self, mesh, cfg):
        return {t: _decode_artifacts(cfg, mesh, t)
                for t in ("bf16", "int8")}

    def test_decode_emits_cache_all_gather(self, artifacts):
        """The sequence-sharded cache must be gathered for attention — the
        single-device jit never exercises this."""
        coll = analysis.hlo_collective_bytes(artifacts["bf16"].as_text())
        assert coll["all-gather"]["count"] > 0
        assert coll["all-gather"]["wire_bytes_bf16eq"] > 0

    def test_int8_decode_moves_s8_payloads(self, artifacts):
        hlo = artifacts["int8"].as_text()
        ag = [l for l in hlo.splitlines()
              if "all-gather(" in l and " = " in l and "-done" not in l]
        assert any("s8[" in l for l in ag), \
            "int8 act transport must put s8 payloads on the gather wire"
        coll = analysis.hlo_collective_bytes(hlo)
        s8 = coll["all-gather"]["wire_bytes_bf16eq_s8"]
        assert s8 > 0
        # and the s8 share dominates the int8 program's gather traffic
        assert s8 > coll["all-gather"]["wire_bytes_bf16eq"] / 2

    def test_int8_gather_wire_below_bf16_over_1p5(self, artifacts):
        coll = {t: analysis.hlo_collective_bytes(a.as_text())
                for t, a in artifacts.items()}
        ag = {t: c["all-gather"]["wire_bytes_bf16eq"]
              for t, c in coll.items()}
        assert ag["int8"] <= ag["bf16"] / 1.5, ag
        # the whole program's wire shrinks too (scales + shared traffic in)
        assert coll["int8"]["total_wire_bytes_bf16eq"] \
            < coll["bf16"]["total_wire_bytes_bf16eq"]

    def test_bf16_baseline_keeps_raw_payload(self, artifacts):
        hlo = artifacts["bf16"].as_text()
        ag = [l for l in hlo.splitlines()
              if "all-gather(" in l and " = " in l and "-done" not in l]
        assert not any("s8[" in l for l in ag)


class TestPrefillActivationGather:
    def test_prefill_int8_gathers_s8(self, mesh, cfg):
        """Prefill's sp residual-stream gather (sequence-sharded post-norm
        activations -> full sequence for attention) carries s8 under the
        int8 transport."""
        p_abs = transformer.abstract_params(cfg)
        p_shard = shd.tree_shardings(p_abs, transformer.param_axes(cfg),
                                     mesh, RULES)
        batch = {"tokens": jax.ShapeDtypeStruct((BATCH, 64), jnp.int32)}
        fn = step_lib.make_prefill_step(cfg, "int8")
        jfn = jax.jit(fn, in_shardings=(p_shard, None))
        with shd.axis_rules(mesh, RULES):
            hlo = jfn.lower(p_abs, batch).compile().as_text()
        coll = analysis.hlo_collective_bytes(hlo)
        assert coll["all-gather"]["wire_bytes_bf16eq_s8"] > 0


class TestGreedyEquivalence:
    @pytest.fixture(scope="class")
    def setup(self, cfg):
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        rng = np.random.RandomState(0)
        prompts = rng.randint(0, cfg.vocab, size=(8, 16)).astype(np.int32)
        lens = rng.randint(8, 17, size=(8,)).astype(np.int32)
        return params, prompts, lens

    def test_int8_greedy_token_identical_to_bf16(self, mesh, cfg, setup):
        """The acceptance criterion: on the smoke config the quantized
        activation gather must not flip a single greedy token."""
        params, prompts, lens = setup
        outs = {t: generate(cfg, params, prompts, max_new=12,
                            prompt_lens=lens, mesh=mesh, act_transport=t)
                for t in ("bf16", "int8")}
        assert (outs["bf16"] == outs["int8"]).all(), outs

    def test_mesh_serving_tracks_single_device(self, mesh, cfg, setup):
        """Mesh placement is a layout change, not a model change: most rows
        must match the single-device run exactly (argmax near-ties under a
        different reduction order may flip an occasional row, which then
        compounds — so gate on row agreement, not full equality)."""
        params, prompts, lens = setup
        single = generate(cfg, params, prompts, max_new=12, prompt_lens=lens)
        meshed = generate(cfg, params, prompts, max_new=12, prompt_lens=lens,
                          mesh=mesh)
        rows_equal = (single == meshed).all(axis=1)
        assert rows_equal.mean() >= 0.5, (single, meshed)
