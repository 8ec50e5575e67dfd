"""Driver: the model server's slot engine (``launch/serve.py::generate``,
``stream="slots"``) on a model configuration at its published widths.

Set-up builds the configuration's weights on the device from the seed
in one jitted call (``bench/harness/weights.py``), in bf16 as they are
served, and warms every program the window runs with one short call of
the same shapes (``warm_new`` new tokens against the same horizon).

One iteration is one ``generate`` call: ``requests`` requests into
``slots`` slots, each prompt padded to ``prompt_pad`` with its real
length from a fixed set drawn once from ``lengths_seed`` (the same set
for every seed and call, in an order drawn from the seed and the call)
and its tokens drawn from the seed and the call, ``max_new`` greedy new
tokens each, decode horizon ``horizon``. Host span: ``generate``.

The latent cache is served in bf16, the precision the configuration
states (``torch_dtype``).

The check, once the window has closed and the weights are freed: a
sample of the window's requests drawn from the seed, the one with the
longest prompt among them, runs through the float32 reference
(``bench/reference/mla.py``) over its prompt and served tokens. Two
numbers are compared, both from the gap by which a served token's logit
lies below the reference's best logit at its position: the widest gap
(a token far off) and the mean gap over every served token of the
sample (a lower precision throughout, such as int8, which moves many
tokens a little).
"""

from __future__ import annotations

import math
import time

import numpy as np

from bench.harness import weights as wlib
from bench.reference import mla as mla_ref

# the numbers the check compares, each against its limit
COMPARED = ("served_token_gap", "mean_gap")

# configuration keys and the program's ModelConfig fields they set
FIELDS = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
          "num_attention_heads": "n_heads",
          "num_key_value_heads": "n_kv_heads",
          "intermediate_size": "d_ff", "vocab_size": "vocab",
          "q_lora_rank": "q_lora_rank", "kv_lora_rank": "kv_lora_rank",
          "qk_rope_head_dim": "rope_head_dim",
          "qk_nope_head_dim": "nope_head_dim", "v_head_dim": "v_head_dim",
          "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
          "tie_word_embeddings": "tie_embeddings"}


def program_config(config: dict):
    """The program's ModelConfig for ``config``. The program has no
    setting for MiniCPM3's scalings or RoPE scaling: it runs a
    configuration only where those leave the computation unchanged."""
    import dataclasses

    from repro.configs import get_config

    neutral = {"scale_emb": 1.0, "dim_model_base": config["hidden_size"],
               "scale_depth": math.sqrt(config["num_hidden_layers"])}
    for key, want in neutral.items():
        if not math.isclose(config[key], want, rel_tol=1e-9):
            raise ValueError(f"the program cannot run {key}={config[key]} "
                             f"(it computes as {key}={want})")
    if config.get("rope_scaling") is not None:
        raise ValueError("the program has no RoPE scaling")
    base = get_config(config["program_arch"])
    return dataclasses.replace(
        base, name=config["name"],
        **{field: config[key] for key, field in FIELDS.items()})


def prompt_lengths(traffic: dict) -> np.ndarray:
    """The fixed set of real prompt lengths: log-normal around
    ``median``, clipped to [``min``, ``max``]."""
    p = traffic["prompt_len"]
    rng = np.random.default_rng(traffic["lengths_seed"])
    x = np.exp(math.log(p["median"]) + p["sigma"] *
               rng.standard_normal(traffic["requests"]))
    return np.clip(np.round(x), p["min"], p["max"]).astype(np.int32)


class Driver:
    def __init__(self, config: dict, traffic: dict, seed: int, log):
        self.config, self.traffic, self.seed, self.log = \
            config, traffic, seed, log
        self.lengths = prompt_lengths(traffic)
        self.calls = []

    def requests(self, call: int):
        """(prompts (n, prompt_pad) int32 zero-padded, real lengths)."""
        t = self.traffic
        rng = np.random.default_rng([self.seed, call + 1])
        lens = self.lengths[rng.permutation(len(self.lengths))]
        prompts = rng.integers(0, self.config["vocab_size"],
                               (len(lens), t["prompt_pad"]), dtype=np.int32)
        prompts[np.arange(t["prompt_pad"])[None, :] >= lens[:, None]] = 0
        return prompts, lens

    # -- set-up ------------------------------------------------------------
    def setup(self):
        import jax

        from repro.dist import sharding as shd
        from repro.launch import serve
        from repro.launch.mesh import make_local_mesh
        from repro.models import transformer

        t = self.traffic
        self.serve = serve
        self.cfg = program_config(self.config)
        abstract = transformer.abstract_params(self.cfg)
        got, want = wlib.shapes_of(abstract), mla_ref.model_shapes(
            self.config)
        if got != want:
            raise ValueError(f"the program's weights {got} are not the "
                             f"model the reference computes {want}")
        mesh = make_local_mesh(model_parallel=1)
        rules = shd.PRESETS["serve_sp"]
        t0 = time.perf_counter()
        self.params = jax.block_until_ready(wlib.build(
            self.seed, abstract, shd.tree_shardings(
                abstract, transformer.param_axes(self.cfg), mesh, rules)))
        self.kwargs = dict(temperature=0.0, mesh=mesh, rules=rules,
                           stream="slots", slots=t["slots"],
                           horizon=t["horizon"], kv_storage="bf16")
        t1 = time.perf_counter()
        prompts, lens = self.requests(-1)
        serve.generate(self.cfg, self.params, prompts, prompt_lens=lens,
                       max_new=min(t["warm_new"], t["max_new"]),
                       **self.kwargs)
        self.log(f"[serving] weights made in {t1 - t0:.3f} s; warm-up call "
                 f"{time.perf_counter() - t1:.3f} s")

    # -- one iteration -----------------------------------------------------
    def step(self):
        from jax.profiler import TraceAnnotation

        prompts, lens = self.requests(len(self.calls))
        t0 = time.perf_counter()
        with TraceAnnotation("generate"):
            out = self.serve.generate(self.cfg, self.params, prompts,
                                      prompt_lens=lens,
                                      max_new=self.traffic["max_new"],
                                      **self.kwargs)
        stats = self.serve._generate_slots.last_stats
        self.calls.append({"lens": lens, "out": np.asarray(out),
                           "decode_steps": int(stats["decode_steps"])})
        self.log(f"[serving] call {len(self.calls) - 1}: "
                 f"{time.perf_counter() - t0:.3f} s, "
                 f"{stats['decode_steps']} decode steps, transfer wait "
                 f"{stats['transfer_wait_s']:.3f} s")

    # -- results -----------------------------------------------------------
    def end_to_end(self, window_s: float) -> dict:
        tokens = sum(c["out"].size for c in self.calls)
        steps = sum(c["decode_steps"] for c in self.calls)
        return {"serve_tok_s": tokens / window_s,
                "itl_ms": 1e3 * window_s / steps}

    def records(self) -> list:
        t = self.traffic
        return [{"lens": c["lens"].tolist(), "max_new": t["max_new"],
                 "slots": t["slots"], "decode_steps": c["decode_steps"]}
                for c in self.calls]

    def counts(self):
        """Requests sent, and those that came back without ``max_new``
        tokens in the vocabulary."""
        attempted = failed = 0
        for c in self.calls:
            n, out = len(c["lens"]), c["out"]
            attempted += n
            if out.shape != (n, self.traffic["max_new"]):
                failed += n
            else:
                failed += int(((out < 0) | (out >= self.config["vocab_size"])
                               ).any(axis=1).sum())
        return attempted, failed

    def release(self):
        self.params = None

    def sample(self):
        """(call, request) pairs checked: drawn from the seed, the longest
        prompt among them."""
        n = self.traffic["check_requests"]
        pairs = [(i, r) for i, c in enumerate(self.calls)
                 for r in range(len(c["lens"]))]
        rng = np.random.default_rng([self.seed, 0])
        longest = max(c["lens"].max() for c in self.calls)
        top = [p for p in pairs if self.calls[p[0]]["lens"][p[1]] == longest]
        first = top[int(rng.integers(len(top)))]
        rest = [p for p in pairs if p != first]
        pick = rng.choice(len(rest), size=min(n - 1, len(rest)),
                          replace=False)
        return [first] + [rest[k] for k in sorted(pick)]

    def sequences(self):
        """Reference inputs of the sample: tokens (B, S), the positions
        whose logits chose the served tokens (B, max_new), and those
        tokens."""
        t = self.traffic
        new = t["max_new"]
        sample = self.sample()
        seq = np.zeros((len(sample), t["prompt_pad"] + new - 1), np.int32)
        picks = np.zeros((len(sample), new), np.int32)
        served = np.zeros((len(sample), new), np.int32)
        for b, (i, r) in enumerate(sample):
            prompts, lens = self.requests(i)
            n = int(lens[r])
            out = self.calls[i]["out"][r]
            seq[b, :n] = prompts[r, :n]
            seq[b, n:n + new - 1] = out[:-1]
            picks[b] = n - 1 + np.arange(new)
            served[b] = out
        return seq, picks, served

    def gaps(self, quant=None) -> dict:
        """The reference's readings over the sample. Without ``quant``:
        the gap of each served token below the float32 reference's best.
        With ``quant``: the same gap of the token that the reference
        computed in that lower precision puts first (the control)."""
        import jax.numpy as jnp

        seq, picks, served = self.sequences()
        ref = mla_ref.logits(self.config, self.seed, seq, picks)
        best = ref.max(-1)
        if quant is None:
            chosen = jnp.asarray(served)
        else:
            chosen = mla_ref.logits(self.config, self.seed, seq, picks,
                                    quant=quant).argmax(-1)
        gap = best - jnp.take_along_axis(ref, chosen[..., None], -1)[..., 0]
        gap = np.asarray(gap)
        return {"served_token_gap": float(gap.max()),
                "mean_gap": float(gap.mean()),
                "agree": int((gap == 0).sum()), "tokens": int(gap.size),
                "row_std": float(np.asarray(ref.std(-1)).mean())}

    def check(self, quant=None) -> dict:
        """The numbers compared; with ``quant``, the control's readings
        of them (``gaps``)."""
        if not self.calls:
            return {k: 1e30 for k in COMPARED}     # nothing was served
        g = self.readings = self.gaps(quant)
        self.log(f"[serving] reference over {g['tokens']} "
                 f"{'served' if quant is None else quant} tokens: "
                 f"{g['agree']} are its argmax, widest gap "
                 f"{g['served_token_gap']:.6g}, mean gap {g['mean_gap']:.6g}, "
                 f"mean row std {g['row_std']:.6g}")
        return {k: g[k] for k in COMPARED}
