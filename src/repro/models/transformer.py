"""Model assembly: embeddings -> layer stack -> head, for all families.

Homogeneous stacks (dense / moe / mla / hybrid / encoder / vlm) store layer
parameters with a leading ``layers`` axis and run under ``lax.scan`` with
full rematerialization, so HLO size and activation memory are O(1) in depth.
The "mla_moe" stack is not homogeneous (``first_k_dense`` leading dense
layers, then held-share expert layers): its norms and attention are
stacked over every layer and scanned as above, with one cache leaf per
kind over all layers, while its FFN weights are two stacks of their own
(``dense_mlp``, ``moe``) that each layer picks from by its index.
xLSTM stacks are heterogeneous (alternating mLSTM/sLSTM) and use a Python
loop (12 layers).

``forward(cfg, params, batch, mode, cache, cache_len_total)``:
  mode="train"   -> (loss, metrics)
  mode="prefill" -> (last-position logits, cache)
  mode="decode"  -> (logits, new_cache)   [batch["pos"] = scalar position]
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs import ModelConfig
from repro.dist import collectives
from repro.dist.collectives import act_gather
from repro.dist.sharding import constrain
from repro.models import attention, moe, ssm, xlstm
from repro.models.common import (
    Spec, rms_norm, swiglu, softmax_xent, stack_layer_specs,
    tree_abstract, tree_axes, tree_init,
)

VIT_HIDDEN = 1024    # stub InternViT output dim
AUDIO_HIDDEN = 512   # stub conv-frontend output dim

SCANNED_FAMILIES = ("dense", "moe", "mla", "mla_moe", "hybrid",
                    "encoder_audio", "vlm")


# ---------------------------------------------------------------------------
# parameter specs
# ---------------------------------------------------------------------------

def layer_specs(cfg: ModelConfig) -> Dict[str, Any]:
    s: Dict[str, Any] = {"ln1": Spec((cfg.d_model,), ("embed",), init="ones"),
                         "ln2": Spec((cfg.d_model,), ("embed",), init="ones")}
    if cfg.mla:
        s["attn"] = attention.mla_specs(cfg)
    else:
        s["attn"] = attention.gqa_specs(cfg)
    if cfg.family == "hybrid":
        s["ssm"] = ssm.ssm_specs(cfg)
    if cfg.family == "moe":
        s["moe"] = moe.moe_specs(cfg)
    elif cfg.family == "mla_moe":
        pass                             # FFN stacks of their own
    elif cfg.d_ff > 0:
        s["mlp"] = _mlp_specs(cfg)
    return s


def _mlp_specs(cfg: ModelConfig) -> Dict[str, Spec]:
    return {"gate": Spec((cfg.d_model, cfg.d_ff), ("embed", "mlp")),
            "up": Spec((cfg.d_model, cfg.d_ff), ("embed", "mlp")),
            "down": Spec((cfg.d_ff, cfg.d_model), ("mlp", "embed"))}


def param_specs(cfg: ModelConfig) -> Dict[str, Any]:
    d, v = cfg.d_model, cfg.vocab
    specs: Dict[str, Any] = {
        "embed": Spec((v, d), ("vocab" if cfg.tie_embeddings else "vocab_in",
                               "embed")),
        "final_norm": Spec((d,), ("embed",), init="ones"),
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = Spec((d, v), ("embed", "vocab"))
    if cfg.frontend == "vit_patches":
        specs["vision_adapter"] = Spec((VIT_HIDDEN, d), (None, "embed"))
    if cfg.frontend == "audio_frames":
        specs["audio_adapter"] = Spec((AUDIO_HIDDEN, d), (None, "embed"))
    if cfg.family == "ssm_xlstm":
        specs["blocks"] = [
            xlstm.mlstm_specs(cfg) if xlstm.is_mlstm_layer(cfg, i)
            else xlstm.slstm_specs(cfg)
            for i in range(cfg.n_layers)]
    else:
        specs["layers"] = stack_layer_specs(layer_specs(cfg), cfg.n_layers)
    if cfg.family == "mla_moe":
        if cfg.first_k_dense:
            specs["dense_mlp"] = stack_layer_specs(_mlp_specs(cfg),
                                                   cfg.first_k_dense)
        specs["moe"] = stack_layer_specs(moe.held_moe_specs(cfg),
                                         cfg.n_layers - cfg.first_k_dense)
    return specs


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------

# Cache leaves that carry attention KV state — the leaves a quantized
# resident cache stores compressed: kv_storage="int8" as s8 values + f32
# scales blocked along the trailing feature axis, kv_storage="f8" as
# scale-free e4m3 values (collectives.cast_f8). Recurrent-state leaves
# (ssm_*, xlstm blocks) are never storage-quantized.
QUANTIZABLE_CACHE_KEYS = ("k", "v", "latent", "k_rope")


def cache_struct(cfg: ModelConfig, batch: int, seq: int,
                 kv_storage: str = "bf16") -> Dict[str, Any]:
    """Shapes (python ints) for the decode cache; no allocation.

    ``kv_storage="int8"`` adds a ``<leaf>_scale`` entry per attention leaf
    (shape = leaf shape with the trailing feature dim replaced by its
    per-position block count); ``"f8"`` keeps the bf16 shapes — e4m3 is
    scale-free, only the leaf dtype changes."""
    if kv_storage not in collectives.KV_STORAGES:
        raise ValueError(f"unknown kv_storage {kv_storage!r}; "
                         f"expected one of {collectives.KV_STORAGES}")
    if cfg.family == "ssm_xlstm":
        return {"blocks": [
            (xlstm.mlstm_cache_shape(cfg, batch)
             if xlstm.is_mlstm_layer(cfg, i)
             else xlstm.slstm_cache_shape(cfg, batch))
            for i in range(cfg.n_layers)]}
    if cfg.mla:
        per = attention.mla_cache_shape(cfg, batch, seq)
    else:
        per = attention.gqa_cache_shape(cfg, batch, seq)
    out = {k: (cfg.n_layers,) + v for k, v in per.items()}
    if cfg.family == "hybrid":
        for k, v in ssm.ssm_cache_shape(cfg, batch).items():
            out["ssm_" + k] = (cfg.n_layers,) + v
    if kv_storage == "int8":
        for k in [k for k in out if k in QUANTIZABLE_CACHE_KEYS]:
            shape = out[k]
            _, nb = collectives.lastdim_blocks(shape[-1])
            out[k + "_scale"] = shape[:-1] + (nb,)
    return out


def _flat_cache_axes(cfg: ModelConfig) -> Dict[str, Any]:
    """Assemble the flat-cache leaf axes from the family modules' StateStore
    contributions (each family declares its per-layer leaf layout; the
    stack prepends "layers" and derives each quantization-scale leaf as
    its value leaf's layout with the trailing block axis unsharded)."""
    if cfg.mla:
        per = attention.mla_cache_axes()
    else:
        per = attention.gqa_cache_axes()
    out = {k: ("layers",) + v for k, v in per.items()}
    if cfg.family == "hybrid":
        for k, v in ssm.ssm_cache_axes().items():
            out["ssm_" + k] = ("layers",) + v
    for k in QUANTIZABLE_CACHE_KEYS:
        if k in out:
            out[k + "_scale"] = out[k][:-1] + (None,)
    return out


def cache_axes(cfg: ModelConfig, batch: int, seq: int,
               kv_storage: str = "bf16") -> Dict[str, Any]:
    struct = cache_struct(cfg, batch, seq, kv_storage)
    if cfg.family == "ssm_xlstm":
        return {"blocks": [
            {k: ("batch",) + (None,) * (len(v) - 1) for k, v in blk.items()}
            for blk in struct["blocks"]]}
    axes = _flat_cache_axes(cfg)
    return {k: axes[k] for k in struct}


def _cache_leaf_dtype(name: Optional[str], kv_storage: str, dtype):
    if kv_storage == "bf16" or name is None:
        return dtype
    if name.endswith("_scale"):
        return jnp.float32
    if name in QUANTIZABLE_CACHE_KEYS:
        return jnp.int8 if kv_storage == "int8" else collectives.F8_DTYPE
    return dtype


def abstract_cache(cfg: ModelConfig, batch: int, seq: int,
                   dtype=jnp.bfloat16, kv_storage: str = "bf16"
                   ) -> Dict[str, Any]:
    def mk(shape, name=None):
        return jax.ShapeDtypeStruct(
            shape, _cache_leaf_dtype(name, kv_storage, dtype))
    struct = cache_struct(cfg, batch, seq, kv_storage)
    if cfg.family == "ssm_xlstm":
        return {"blocks": [{k: mk(v) for k, v in blk.items()}
                           for blk in struct["blocks"]]}
    return {k: mk(v, k) for k, v in struct.items()}


def init_cache(cfg: ModelConfig, batch: int, seq: int, dtype=jnp.bfloat16,
               kv_storage: str = "bf16"):
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        abstract_cache(cfg, batch, seq, dtype, kv_storage))


def quantize_cache_int8(cache: Dict[str, Any]) -> Dict[str, Any]:
    """Convert a bf16 decode cache into the int8-resident storage layout:
    every attention leaf becomes s8 values + a ``<leaf>_scale`` f32 leaf,
    quantized blockwise along the trailing feature axis (per position —
    matching what the decode step writes for each new token). Recurrent
    leaves pass through untouched. jit-compatible."""
    out: Dict[str, Any] = {}
    for name, leaf in cache.items():
        if name in QUANTIZABLE_CACHE_KEYS:
            q, s = collectives.quantize_int8_lastdim(leaf)
            out[name] = q
            out[name + "_scale"] = s
        else:
            out[name] = leaf
    return out


def quantize_cache(cache: Dict[str, Any], kv_storage: str) -> Dict[str, Any]:
    """Convert a bf16 decode cache (or cache slice) into the resident
    storage layout for ``kv_storage`` — identity for "bf16", s8 + scales
    for "int8", scale-free e4m3 for "f8". jit-compatible; the slot
    admission step and the whole-batch handoff both route through here."""
    if kv_storage == "bf16":
        return cache
    if kv_storage == "int8":
        return quantize_cache_int8(cache)
    if kv_storage == "f8":
        return {name: collectives.cast_f8(leaf)
                if name in QUANTIZABLE_CACHE_KEYS else leaf
                for name, leaf in cache.items()}
    raise ValueError(f"unknown kv_storage {kv_storage!r}; "
                     f"expected one of {collectives.KV_STORAGES}")


# ---------------------------------------------------------------------------
# layer body (scanned families)
# ---------------------------------------------------------------------------

def _held_ffn(cfg: ModelConfig, ffn, h, layer, mode):
    """The "mla_moe" FFN of layer ``layer`` (traced): the dense MLP of a
    leading layer or the held-share expert layer, each indexed out of its
    own stack. Returns (y, counters); a dense layer counts nothing."""
    k = cfg.first_k_dense

    def experts(h):
        i = jnp.maximum(layer - k, 0)
        p = {n: jax.tree.map(lambda t: t[i], v) for n, v in ffn["moe"].items()
             if n not in ("w_gate", "w_up", "w_down")}
        return moe.held_moe_apply(cfg, p, ffn["moe"], h, i, mode)

    def dense(h):
        p = jax.tree.map(lambda t: t[jnp.minimum(layer, k - 1)],
                         ffn["dense_mlp"])
        zero = jnp.zeros((), jnp.int32)
        return (swiglu(h, p["gate"], p["up"], p["down"]),
                {n: zero for n in moe.HELD_COUNTERS})

    if not k:
        return experts(h)
    return jax.lax.cond(layer < k, dense, experts, h)


def _layer_body(cfg: ModelConfig, mode: str, cache_len_total: int,
                x, lp, lcache, pos, layer=None, ffn=None):
    aux = {}
    # residual stream anchor; under the "sp"/"serve_sp" presets seq_res ->
    # model shards the residual stream (Megatron sequence parallelism)
    x = constrain(x, "batch", "seq_res", "act_embed")
    h = rms_norm(x, lp["ln1"], cfg.norm_eps)
    if mode != "decode":
        # the sp activation all-gather: attention needs the full sequence,
        # so the post-norm stream reshards seq-sharded -> gathered here
        # (int8 on the wire under act_transport="int8"). Decode's gather
        # is the KV-cache gather inside the attention layer instead.
        h = act_gather(h, "batch", None, "act_embed")
    attn_cache = None
    if lcache is not None and cfg.family != "hybrid":
        attn_cache = lcache
    elif lcache is not None:
        attn_cache = {"k": lcache["k"], "v": lcache["v"]}
    if cfg.mla:
        attn_out, new_attn = attention.mla_apply(
            cfg, lp["attn"], h, mode, attn_cache, pos, cache_len_total)
    else:
        attn_out, new_attn = attention.gqa_apply(
            cfg, lp["attn"], h, mode, attn_cache, pos, cache_len_total)
    if cfg.family == "hybrid":
        ssm_cache = None
        if lcache is not None:
            ssm_cache = {"conv": lcache["ssm_conv"], "ssm": lcache["ssm_ssm"]}
        ssm_out, new_ssm = ssm.ssm_apply(cfg, lp["ssm"], h, mode, ssm_cache)
        x = x + 0.5 * (attn_out + ssm_out)
    else:
        x = x + attn_out
    h2 = rms_norm(x, lp["ln2"], cfg.norm_eps)
    if mode != "decode":
        h2 = act_gather(h2, "batch", None, "act_embed")   # sp gather, MLP side
    if cfg.family == "moe":
        y, aux = moe.moe_apply(cfg, lp["moe"], h2, mode=mode)
    elif cfg.family == "mla_moe":
        y, aux = _held_ffn(cfg, ffn, h2, layer, mode)
    elif cfg.d_ff > 0:
        y = swiglu(h2, lp["mlp"]["gate"], lp["mlp"]["up"], lp["mlp"]["down"])
    else:
        y = jnp.zeros_like(x)
    x = x + y

    new_cache = None
    if new_attn is not None:
        new_cache = dict(new_attn)
        if cfg.family == "hybrid":
            new_cache = {"k": new_attn["k"], "v": new_attn["v"],
                         "ssm_conv": new_ssm["conv"], "ssm_ssm": new_ssm["ssm"]}
    return x, new_cache, aux


def _run_stack(cfg, params, x, mode, cache, pos, cache_len_total):
    """Scan the homogeneous layer stack. Returns (x, new_cache, aux).

    ``cfg.remat_block`` layers form one rematerialization unit: only the
    unit's input is saved for backward, so saved-activation memory scales
    as L / remat_block (at the cost of re-running the whole unit forward in
    backward — flops unchanged under full remat, one extra unit-input copy).
    """
    has_cache = cache is not None and mode in ("decode",)
    emits_cache = mode in ("decode", "prefill")
    rb = max(1, cfg.remat_block)
    n_units = cfg.n_layers // rb
    assert cfg.n_layers % rb == 0, (cfg.n_layers, rb)

    held = cfg.family == "mla_moe"
    ffn = {k: params[k] for k in ("dense_mlp", "moe") if k in params} \
        if held else None

    def unit_body(xcur, lp_unit, lcache_unit, idx_unit=None, *, pos):
        caches = []
        aux_tot = {}
        for j in range(rb):
            lp = jax.tree.map(lambda t: t[j], lp_unit)
            lcache = jax.tree.map(lambda t: t[j], lcache_unit) \
                if lcache_unit is not None else None
            layer = None if idx_unit is None else idx_unit[j]
            xcur, new_lcache, aux = _layer_body(
                cfg, mode, cache_len_total, xcur, lp, lcache, pos,
                layer, ffn)
            caches.append(new_lcache)
            aux_tot = moe.add_counters(aux_tot, aux or {})
        if caches[0] is not None:
            caches = jax.tree.map(lambda *ts: jnp.stack(ts), *caches)
        else:
            caches = None
        return xcur, caches, aux_tot

    body = jax.checkpoint(partial(unit_body, pos=pos))

    def scan_fn(carry, xs):
        xcur, aux_acc = carry
        xnew, new_lcache, aux = body(xcur, *xs)
        aux_acc = moe.add_counters(aux_acc, aux) if aux else aux_acc
        return (xnew, aux_acc), new_lcache

    aux0 = {}
    if cfg.family == "moe":
        aux0 = {"moe_lb_loss": jnp.zeros((), jnp.float32),
                "moe_z_loss": jnp.zeros((), jnp.float32),
                "moe_drop_frac": jnp.zeros((), jnp.float32)}
    if held:
        aux0 = {k: jnp.zeros((), jnp.int32) for k in moe.HELD_COUNTERS}

    def to_units(t):
        return t.reshape(n_units, rb, *t.shape[1:])

    lp_units = jax.tree.map(to_units, params["layers"])
    xs_cache = jax.tree.map(to_units, cache) if has_cache else None
    xs = (lp_units, xs_cache)
    if held:            # each layer's index, to pick its FFN weights
        xs += (to_units(jnp.arange(cfg.n_layers, dtype=jnp.int32)),)
    (x, aux), new_cache = jax.lax.scan(scan_fn, (x, aux0), xs)
    if not emits_cache:
        new_cache = None
    elif new_cache is not None:
        new_cache = jax.tree.map(
            lambda t: t.reshape(cfg.n_layers, *t.shape[2:]), new_cache)
    if cfg.family == "moe":
        aux = {k: v / cfg.n_layers for k, v in aux.items()}
    return x, new_cache, aux


def _run_xlstm(cfg, params, x, mode, cache):
    new_blocks = []
    blocks_cache = cache["blocks"] if cache is not None else [None] * cfg.n_layers
    for i, bp in enumerate(params["blocks"]):
        fn = xlstm.mlstm_apply if xlstm.is_mlstm_layer(cfg, i) else xlstm.slstm_apply
        x, bc = jax.checkpoint(partial(fn, cfg), static_argnums=(2,))(
            bp, x, mode, blocks_cache[i])
        new_blocks.append(bc)
    if mode in ("decode", "prefill"):
        return x, {"blocks": new_blocks}, {}
    return x, None, {}


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _embed_inputs(cfg, params, batch, mode):
    if cfg.frontend == "audio_frames":
        return constrain(jnp.einsum("bsf,fd->bsd", batch["frames"],
                                    params["audio_adapter"]),
                         "batch", None, "act_embed")
    tok = jnp.take(params["embed"], batch["tokens"], axis=0)
    tok = constrain(tok, "batch", None, "act_embed")
    if cfg.frontend == "vit_patches" and mode != "decode":
        vis = jnp.einsum("bpf,fd->bpd", batch["patches"],
                         params["vision_adapter"])
        return constrain(jnp.concatenate([vis, tok], axis=1),
                         "batch", None, "act_embed")
    return tok


def _logits(cfg, params, x):
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    out = jnp.einsum("...d,dv->...v", x, head)
    return constrain(out, *(("batch",) + (None,) * (out.ndim - 2) + ("vocab",)))


def forward(cfg: ModelConfig, params, batch: Dict[str, Any], mode: str,
            cache=None, cache_len_total: int = 0, return_aux: bool = False):
    """``return_aux`` (decode): also return the stack's counters, e.g. the
    held-share expert layer's ``moe.HELD_COUNTERS`` summed over layers."""
    x = _embed_inputs(cfg, params, batch, mode)
    pos = batch.get("pos", 0)

    if cfg.family == "ssm_xlstm":
        x, new_cache, aux = _run_xlstm(cfg, params, x, mode, cache)
    else:
        x, new_cache, aux = _run_stack(cfg, params, x, mode, cache, pos,
                                       cache_len_total)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)

    if mode == "train":
        if cfg.frontend == "vit_patches":
            x = x[:, cfg.n_vision_tokens:]       # loss on text positions only
        logits = _logits(cfg, params, x)
        loss = softmax_xent(logits, batch["labels"], batch.get("mask"))
        metrics = {"ce_loss": loss}
        if cfg.family == "moe":
            loss = loss + 0.01 * aux["moe_lb_loss"] \
                + cfg.router_aux_weight * aux["moe_z_loss"]
            metrics.update(aux)
        metrics["loss"] = loss
        return loss, metrics

    if mode == "encode":  # encoder-only serving: per-position unit logits
        return _logits(cfg, params, x), None

    if mode == "prefill":
        last = batch.get("last_pos")
        if last is None:
            xl = x[:, -1]
        else:   # ragged prompts: per-row index of the final prompt token
            idx = jnp.asarray(last, jnp.int32)[:, None, None]
            xl = jnp.take_along_axis(x, idx, axis=1)[:, 0]
        return _logits(cfg, params, xl), new_cache

    # decode
    logits = _logits(cfg, params, x[:, -1])
    if return_aux:
        return logits, new_cache, aux
    return logits, new_cache


# ---------------------------------------------------------------------------
# public param API
# ---------------------------------------------------------------------------

def init_params(cfg: ModelConfig, key, shardings=None):
    """Random parameters from ``key``. With ``shardings`` (one sharding
    per leaf, e.g. ``dist.sharding.tree_shardings`` of the mesh that runs
    them) the tree is built under jit straight into that placement, so no
    device ever holds an unplaced full copy."""
    placed = {} if shardings is None else {"out_shardings": shardings}
    return jax.jit(lambda k: tree_init(param_specs(cfg), k), **placed)(key)


def abstract_params(cfg: ModelConfig):
    return tree_abstract(param_specs(cfg))


def param_axes(cfg: ModelConfig):
    return tree_axes(param_specs(cfg))
