"""The MLA + held-share expert model (family "mla_moe", Moonlight-16B-A3B's
DeepSeek-V3 block) against the plain float32 reference
(``bench/reference/mla_moe.py``) at a small size on the CPU.

The program runs here in float32 end to end (weights, activations and
the latent cache; the grouped-matmul kernel interpreted), so that it and
the reference differ only by the order of float32 sums, and every
tolerance can be tight: each is written beside its check."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.drivers import serving_moe
from bench.harness import weights as wlib
from bench.harness import weights_moe as wmoe
from bench.reference import mla_moe as ref
from bench.tests.serving_moe_tiny import TINY_MODEL
from repro.configs import get_config, smoke_config
from repro.models import attention, common, moe, transformer
from repro.train import step as step_lib

F32 = jnp.float32
SEED = 2 ** 33 + 17

# Program logits within this share of the logits' largest magnitude of
# the float32 reference. Both compute in float32 from the same weights:
# they differ by the order of sums (the readings: 4.7e-7 scalar, 4.4e-7
# ragged). A reference whose expert weights are rounded to bf16 moves the
# logits by 1.9e-3 of the scale, which this fails by 39 times
# (test_tolerance_sees_bf16_expert_weights).
LOGIT_TOL = 5e-5


def _rc(**over):
    """The reference's configuration: Moonlight's file with the tiny
    widths, 8 routed experts of which experts 4-7 are held."""
    from bench.harness import core
    rc = core.load_json("configs", "moonlight-16b-a3b")
    rc.update(TINY_MODEL, **over)
    return rc


def _params(rc, dtype=F32, seed=SEED):
    cfg = serving_moe.program_config(rc)
    abstract = transformer.abstract_params(cfg)
    return cfg, wmoe.build(seed, abstract, rc["router_bias_std"],
                           dtype=dtype)


def _strip(tree, prefix):
    out = {}
    for kp, v in jax.tree_util.tree_flatten_with_path(tree[prefix])[0]:
        out[wlib.path_str(kp)] = v
    return out


def _layer(tree, l):
    return {k: v[l].astype(F32) for k, v in tree.items()}


def ref_forward(rc, params, tokens, expert_round=None):
    """The reference's full forward over ``tokens`` (B, S) with the
    program's weights: logits (B, S, V) and, per expert layer, which
    experts each token chose (B, S, E)."""
    k = rc["first_k_dense_replace"]
    attn = _strip(params, "layers")
    dense = _strip(params, "dense_mlp") if k else {}
    sparse = _strip(params, "moe")
    if expert_round is not None:
        for n in ("w_gate", "w_up", "w_down"):
            sparse[n] = sparse[n].astype(expert_round).astype(F32)

    @jax.jit
    def run(tokens):
        with jax.default_matmul_precision("highest"):
            x = jnp.take(params["embed"].astype(F32), tokens, axis=0)
            chosen = []
            for l in range(rc["num_hidden_layers"]):
                if l < k:
                    x = ref._dense_layer(rc, None, x, _layer(attn, l),
                                         _layer(dense, l))
                    continue
                w_a, w_m = _layer(attn, l), _layer(sparse, l - k)
                x = ref._attention(rc, None, x, w_a)
                h = ref._rms(x, w_a["ln2"], rc["rms_norm_eps"])
                chosen.append(ref.routing(rc, w_m["router"],
                                          w_m["router_bias"], h) > 0)
                x = x + ref._moe(rc, None, h, w_m)
            x = ref._rms(x, params["final_norm"].astype(F32),
                         rc["rms_norm_eps"])
            return x @ params["lm_head"].astype(F32), chosen
    return run(jnp.asarray(tokens, jnp.int32))


def program_decode(cfg, params, tokens, lens, steps, monkeypatch):
    """Prefill of the prompts (``lens``, padded to ``tokens``'s width minus
    ``steps``), then ``steps`` teacher-forced decode steps through the
    cache, in float32. Returns logits at each row's last prompt position
    and each decode position (B, 1 + steps, V), and the counters of each
    decode step."""
    monkeypatch.setattr(common, "COMPUTE_DTYPE", F32)
    b = tokens.shape[0]
    s0 = tokens.shape[1] - steps
    total = s0 + steps
    lens = np.asarray(lens, np.int32)
    scalar = bool((lens == lens[0]).all())
    prompts = np.where(np.arange(s0)[None] < lens[:, None],
                       tokens[:, :s0], 0)
    batch = {"tokens": jnp.asarray(prompts)}
    if not scalar:
        batch["last_pos"] = jnp.asarray(lens - 1)
    logits, cache = jax.jit(step_lib.make_prefill_step(cfg))(params, batch)
    target = transformer.abstract_cache(cfg, b, total, dtype=F32)
    cache = jax.tree.map(lambda c, t: jnp.pad(
        c.astype(F32), [(0, tt - cc) for cc, tt in zip(c.shape, t.shape)]),
        cache, target)
    decode = jax.jit(step_lib.make_decode_step(cfg, total))
    out, counts = [logits], []
    for j in range(steps):
        pos = lens + j
        tok = tokens[np.arange(b), pos][:, None]
        p = jnp.asarray(pos[0] if scalar else pos, jnp.int32)
        logits, cache, c = decode(params, cache,
                                  {"tokens": jnp.asarray(tok), "pos": p},
                                  step_lib.decode_counters(cfg))
        out.append(logits)
        counts.append({k: int(v) for k, v in c.items()})
    return jnp.stack(out, 1), counts


CASES = {"scalar": [9, 9, 9], "ragged": [4, 9, 7]}


@pytest.mark.parametrize("case", sorted(CASES))
def test_prefill_then_decode_matches_reference(case, monkeypatch):
    rc = _rc()
    cfg, params = _params(rc)
    steps, lens = 4, np.asarray(CASES[case])
    rng = np.random.default_rng(3)
    tokens = rng.integers(0, rc["vocab_size"], (3, 9 + steps)).astype(
        np.int32)
    got, counts = program_decode(cfg, params, tokens, lens, steps,
                                 monkeypatch)
    want, chosen = ref_forward(rc, params, tokens)
    picks = lens[:, None] - 1 + np.arange(1 + steps)
    want = jnp.take_along_axis(want, jnp.asarray(picks)[..., None], 1)
    scale = float(jnp.max(jnp.abs(want)))
    err = float(jnp.max(jnp.abs(got - want)))
    assert err <= LOGIT_TOL * scale, (err, scale)

    # the counters: pairs routed onto held experts (4-7) by each decode
    # step's tokens, summed over the expert layers, as the reference
    # routes them; and the most tokens one held expert took
    off, eh = rc["expert_offset"], rc["n_routed_experts"]
    for j, c in enumerate(counts):
        at = jnp.asarray(lens + j)
        held = [jnp.take_along_axis(ch[..., off:off + eh],
                                    at[:, None, None], 1)[:, 0]
                for ch in chosen]                   # (B, Eh) per layer
        assert c["moe_held_pairs"] == sum(int(h.sum()) for h in held)
        assert c["moe_max_expert_tokens"] == max(
            int(h.sum(0).max()) for h in held)


def test_tolerance_sees_bf16_expert_weights():
    """The control: the reference with its expert weights rounded to bf16
    is further from the float32 reference than LOGIT_TOL allows."""
    rc = _rc()
    _, params = _params(rc)
    tokens = np.random.default_rng(4).integers(
        0, rc["vocab_size"], (3, 13)).astype(np.int32)
    want, _ = ref_forward(rc, params, tokens)
    rounded, _ = ref_forward(rc, params, tokens, expert_round=jnp.bfloat16)
    err = float(jnp.max(jnp.abs(rounded - want)))
    assert err > 4 * LOGIT_TOL * float(jnp.max(jnp.abs(want)))


def _layer_inputs(rc, seed, t=24):
    """One expert layer's weights (f32, every published expert) and
    normalised inputs of ``t`` tokens."""
    full = dict(rc, n_routed_experts=rc["published"]["n_routed_experts"],
                expert_offset=0)
    _, params = _params(full, seed=seed)
    w = _layer(_strip(params, "moe"), 0)
    x = jax.random.normal(jax.random.PRNGKey(seed), (1, t, rc["hidden_size"]))
    return full, w, ref._rms(x, 1.0, rc["rms_norm_eps"])


def _program_layer(rc, w, x, offset, held):
    """The program's layer holding experts ``offset .. offset+held-1``."""
    cfg = serving_moe.program_config(dict(rc, n_routed_experts=held,
                                          expert_offset=offset))
    experts = {n: w[n][None, offset:offset + held]
               for n in ("w_gate", "w_up", "w_down")}
    p = {n: w[n] for n in ("router", "router_bias")}
    p["shared"] = {n: w["shared/" + n] for n in ("gate", "up", "down")}
    return moe.held_moe_apply(cfg, p, experts, x, jnp.int32(0), "prefill")


# Sums of float32 expert outputs in another order than the reference's:
# the readings sit under 2e-6 of the output's scale.
LAYER_TOL = 2e-5


def test_chip_shares_add_up_to_the_uncut_layer():
    """Eight chips of eight experts each: what each share adds, with the
    shared experts (computed alike on every chip) counted once, is the
    layer over all 64 experts."""
    rc = _rc(n_routed_experts=8, expert_offset=0, num_experts_per_tok=6,
             published={"n_routed_experts": 64})
    full, w, x = _layer_inputs(rc, 11)
    with jax.default_matmul_precision("highest"):
        want = ref._moe(full, None, x, w)
        shared = ref._mlp(x, w["shared/gate"], w["shared/up"],
                          w["shared/down"], None)
        parts = [_program_layer(rc, w, x, 8 * c, 8)[0] - shared
                 for c in range(8)]
    got = shared + sum(parts)
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) <= LAYER_TOL * scale
    # every share adds something: the routing spreads over all chips
    assert all(float(jnp.max(jnp.abs(p))) > 0 for p in parts)


def test_skewed_routing_drops_no_pair():
    """A selection bias that sends every token to one held expert: all of
    them are computed (no capacity), and the layer is the reference's."""
    rc = _rc()
    full, w, x = _layer_inputs(rc, 12, t=40)
    w = dict(w, router_bias=w["router_bias"].at[5].add(10.0))
    held = dict(rc, expert_offset=4, n_routed_experts=4)
    w_held = dict(w, **{n: w[n][4:] for n in ("w_gate", "w_up", "w_down")})
    with jax.default_matmul_precision("highest"):
        want = ref._moe(held, None, x, w_held)
        got, counts = _program_layer(rc, w, x, 4, 4)
    assert int(counts["moe_max_expert_tokens"]) == 40
    chosen = ref.routing(full, w["router"], w["router_bias"], x) > 0
    assert int(counts["moe_held_pairs"]) == int(chosen[..., 4:].sum())
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) <= LAYER_TOL * scale


@pytest.mark.parametrize("pos", [7, (0, 5, 11)])
def test_absorbed_decode_without_q_lora_matches_expanded(pos):
    """MLA with a direct query projection: absorbed decode (the serving
    path) against the expanded form, as test_mla_absorbed.py holds
    MiniCPM3's. Both float32: agreement within 1e-5 of the scale."""
    cfg = smoke_config("moonlight-16b-a3b")
    assert cfg.q_lora_rank == 0
    b, s = 3, 12
    p = common.tree_init(attention.mla_specs(cfg), jax.random.PRNGKey(3))
    assert "wq" in p and "q_norm" not in p and "wq_a" not in p
    p = jax.tree.map(lambda t: t.astype(F32), p)
    x = jax.random.normal(jax.random.PRNGKey(5), (b, 1, cfg.d_model))
    k1, k2 = jax.random.split(jax.random.PRNGKey(6))
    cache = {"latent": jax.random.normal(k1, (b, s, cfg.kv_lora_rank)),
             "k_rope": jax.random.normal(k2, (b, s, 1, cfg.rope_head_dim))}
    pos = jnp.asarray(pos, jnp.int32)
    out, new = jax.jit(lambda c: attention.mla_apply(
        cfg, p, x, "decode", c, pos, s))(cache)
    positions = jnp.broadcast_to(pos[..., None], (b, 1))
    q, _, _ = attention._mla_qk(cfg, p, x, positions)
    k, v = attention._mla_expand(cfg, p, new["latent"],
                                 new["k_rope"][..., 0, :])
    kpos = attention.cache_slot_positions(s + 1, s, pos)
    want = jnp.einsum("bshk,hkd->bsd",
                      common.decode_attention(q, k, v, kpos, pos), p["wo"])
    assert float(jnp.max(jnp.abs(out - want))) <= \
        1e-5 * float(jnp.max(jnp.abs(want)))


def test_published_config_counts():
    """Moonlight-16B-A3B: 16B parameters, 3B active a token; this chip
    holds 3.364B (8 of 64 experts of each expert layer)."""
    cfg = get_config("moonlight-16b-a3b")
    assert abs(cfg.param_count() / 15.96e9 - 1) < 0.005
    assert abs(cfg.active_param_count() / 2.915e9 - 1) < 0.005
    held = sum(int(np.prod(a.shape))
               for a in jax.tree.leaves(transformer.abstract_params(cfg)))
    assert abs(held / 3.3646e9 - 1) < 0.001
    assert (cfg.n_experts, cfg.n_held_experts, cfg.expert_offset) == \
        (64, 8, 0)


def test_slot_engine_reads_the_counters_once():
    """The slot engine serves the model and reports the decode steps'
    counters; with every slot busy, the held pairs are a share of the
    decode pairs."""
    from repro.launch import serve
    cfg = smoke_config("moonlight-16b-a3b")
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    prompts = np.random.default_rng(0).integers(0, cfg.vocab, (3, 8))
    out = serve.generate(cfg, params, prompts.astype(np.int32), max_new=5,
                         stream="slots", slots=3)
    st = serve._generate_slots.last_stats
    pairs = st["decode_steps"] * 3 * cfg.top_k * (cfg.n_layers
                                                  - cfg.first_k_dense)
    assert out.shape == (3, 5)
    assert 0 < st["moe_held_pairs"] <= pairs
    assert 0 < st["moe_max_expert_tokens"] <= 3


# sha256 of minicpm3-4b's smoke prefill_step ([2, 16] prompts with
# last_pos) and decode_step ([2, 1] tokens, per-row pos, a 32-position
# cache), lowered on the CPU with debug information left out, as the MLA
# path computed them before the no-q_lora branch and the expert counters
MINICPM3_HLO = {
    "prefill": "90ca32352793cc53521d93b19567d49ebb5b4c5e87211545e4818aac99132b01",
    "decode": "822d0e95ce84ce8e0efe57d84d53d4e50da1ee93ac8ce8a90cb53ab3ca4c71c8",
}


def test_minicpm3_programs_are_unchanged():
    cfg = smoke_config("minicpm3-4b")
    params = transformer.abstract_params(cfg)
    b, s, t = 2, 16, 32
    sds = jax.ShapeDtypeStruct
    lowered = {
        "prefill": jax.jit(step_lib.make_prefill_step(cfg, "bf16")).lower(
            params, {"tokens": sds((b, s), jnp.int32),
                     "last_pos": sds((b,), jnp.int32)}),
        "decode": jax.jit(step_lib.make_decode_step(cfg, t, "bf16", "bf16")
                          ).lower(params, transformer.abstract_cache(cfg, b, t),
                                  {"tokens": sds((b, 1), jnp.int32),
                                   "pos": sds((b,), jnp.int32)}),
    }
    for name, low in lowered.items():
        text = low.compiler_ir("stablehlo").operation.get_asm(
            enable_debug_info=False)
        assert hashlib.sha256(text.encode()).hexdigest() == \
            MINICPM3_HLO[name], name
