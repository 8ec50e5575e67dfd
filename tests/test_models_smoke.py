"""Per-architecture smoke tests: a REDUCED config of each assigned family
runs one forward/train step on CPU (shape + finiteness asserts), plus
prefill->decode consistency for every decoder arch."""

import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCH_IDS, get_config, smoke_config
from repro.configs.shapes import SHAPES, ShapeSpec, applicable, make_batch
from repro.models import transformer
from repro.train import optimizer as opt_lib
from repro.train import step as step_lib

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def smoke_state():
    cache = {}

    def get(arch):
        if arch not in cache:
            cfg = smoke_config(arch)
            cache[arch] = (cfg, transformer.init_params(cfg, KEY))
        return cache[arch]

    return get


@pytest.mark.parametrize("arch", ARCH_IDS)
class TestArchSmoke:
    def test_forward_train(self, arch, smoke_state):
        cfg, params = smoke_state(arch)
        batch, _ = make_batch(cfg, ShapeSpec("t", "train", 16, 2, 2), KEY)
        loss, metrics = transformer.forward(cfg, params, batch, "train")
        assert loss.shape == ()
        assert bool(jnp.isfinite(loss)), f"{arch}: non-finite loss"
        assert float(loss) > 0

    def test_train_step_updates_params(self, arch, smoke_state):
        cfg, params = smoke_state(arch)
        opt = opt_lib.init_state(params)
        ts = step_lib.make_train_step(cfg, opt_lib.AdamWConfig(),
                                      microbatches=2)
        batch, _ = make_batch(cfg, ShapeSpec("t", "train", 16, 4, 2), KEY)
        new_params, new_opt, metrics = jax.jit(ts)(params, opt, batch)
        assert bool(jnp.isfinite(metrics["loss"]))
        assert int(new_opt["step"]) == 1
        # at least one big leaf actually moved
        moved = any(
            float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                  - b.astype(jnp.float32)))) > 0
            for a, b in zip(jax.tree.leaves(params),
                            jax.tree.leaves(new_params)))
        assert moved

    def test_decode_matches_prefill(self, arch, smoke_state):
        cfg, params = smoke_state(arch)
        if not cfg.supports_decode:
            pytest.skip("encoder-only")
        B, S = 2, 16
        toks = jax.random.randint(KEY, (B, S), 0, cfg.vocab, dtype=jnp.int32)
        batch_full = {"tokens": toks}
        batch_pre = {"tokens": toks[:, :-1]}
        if cfg.frontend == "vit_patches":
            patches = jax.random.normal(
                KEY, (B, cfg.n_vision_tokens, transformer.VIT_HIDDEN),
                jnp.float32).astype(jnp.bfloat16)
            batch_full["patches"] = patches
            batch_pre["patches"] = patches
        full_logits, _ = transformer.forward(cfg, params, batch_full, "prefill")
        _, cache = transformer.forward(cfg, params, batch_pre, "prefill")
        nvis = cfg.n_vision_tokens if cfg.frontend == "vit_patches" else 0
        total = S + nvis
        target = transformer.abstract_cache(cfg, B, total)
        cache = jax.tree.map(
            lambda c, t: jnp.pad(
                c, [(0, tt - ss) for ss, tt in zip(c.shape, t.shape)]
            ).astype(t.dtype), cache, target)
        dec_logits, _ = transformer.forward(
            cfg, params,
            {"tokens": toks[:, -1:], "pos": jnp.asarray(total - 1, jnp.int32)},
            "decode", cache=cache, cache_len_total=total)
        err = float(jnp.max(jnp.abs(dec_logits.astype(jnp.float32)
                                    - full_logits.astype(jnp.float32))))
        scale = float(jnp.max(jnp.abs(full_logits.astype(jnp.float32)))) + 1e-9
        # MoE: dropped-token routing differs between prefill groups and the
        # single-token decode group => inherent small deviation
        tol = 0.12 if cfg.family == "moe" else 0.02
        assert err / scale < tol, f"{arch}: rel err {err/scale:.4f}"

    def test_encoder_encode_mode(self, arch, smoke_state):
        cfg, params = smoke_state(arch)
        if cfg.supports_decode:
            pytest.skip("decoder arch")
        batch, _ = make_batch(cfg, ShapeSpec("p", "prefill", 16, 2), KEY)
        logits, _ = transformer.forward(cfg, params, batch, "encode")
        assert logits.shape == (2, 16, cfg.vocab)
        assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))


class TestFullConfigsAbstract:
    """Full (published) configs are exercised abstractly: parameter counts
    match the advertised sizes and input_specs are well-formed for every
    applicable (arch x shape) cell — no allocation."""

    EXPECTED_PARAMS = {
        "qwen3-moe-235b-a22b": (235e9, 0.10),
        "qwen3-moe-30b-a3b": (30e9, 0.12),
        "qwen1.5-110b": (110e9, 0.08),
        "yi-34b": (34e9, 0.08),
        "minicpm3-4b": (4e9, 0.25),
        "granite-3-8b": (8e9, 0.15),
        "hubert-xlarge": (1e9, 0.4),
        "hymba-1.5b": (1.5e9, 0.4),
        "internvl2-2b": (2e9, 0.25),
        "xlstm-125m": (125e6, 0.4),
        "moonlight-16b-a3b": (16e9, 0.02),
    }

    @pytest.mark.parametrize("arch", ARCH_IDS)
    def test_param_count_matches_published(self, arch):
        cfg = get_config(arch)
        n = cfg.param_count()
        target, tol = self.EXPECTED_PARAMS[arch]
        assert abs(n - target) / target < tol, \
            f"{arch}: {n/1e9:.2f}B vs {target/1e9:.2f}B"

    @pytest.mark.parametrize("arch", ARCH_IDS)
    @pytest.mark.parametrize("shape_name", list(SHAPES))
    def test_input_specs_well_formed(self, arch, shape_name):
        from repro.configs.shapes import input_specs
        cfg = get_config(arch)
        shape = SHAPES[shape_name]
        ok, why = applicable(cfg, shape)
        if not ok:
            with pytest.raises(ValueError):
                input_specs(cfg, shape)
            return
        batch, cache = input_specs(cfg, shape)
        for sds in jax.tree.leaves(batch):
            assert all(d > 0 for d in sds.shape)
        if shape.kind == "decode":
            assert cache is not None

    def test_moe_active_params(self):
        cfg = get_config("qwen3-moe-235b-a22b")
        active = cfg.active_param_count()
        assert 18e9 < active < 26e9           # ~A22B
