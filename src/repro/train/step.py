"""Step factories: train_step (grad-accum microbatching + AdamW) and
serve steps (prefill / decode). These are the functions the launcher jits
with explicit in/out shardings and the dry-run lowers on the production mesh.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs import ModelConfig
from repro.configs.shapes import ShapeSpec
from repro.dist import collectives
from repro.models import moe
from repro.models import registry as model_registry
from repro.models import transformer
from repro.train import optimizer as opt_lib

GRAD_TRANSPORTS = ("bf16", "int8_ef")
ACT_TRANSPORTS = collectives.ACT_TRANSPORTS   # serve steps: ("bf16", "int8")
KV_STORAGES = collectives.KV_STORAGES         # decode cache residency
CACHE_TRANSFERS = collectives.CACHE_TRANSFERS # prefill->decode handoff wire


def make_loss_fn(cfg: ModelConfig):
    def loss_fn(params, batch):
        loss, metrics = transformer.forward(cfg, params, batch, "train")
        return loss, metrics
    return loss_fn


def _split_microbatches(batch: Dict[str, Any], n_mb: int) -> Dict[str, Any]:
    def split(x):
        b = x.shape[0]
        assert b % n_mb == 0, (b, n_mb)
        return x.reshape(n_mb, b // n_mb, *x.shape[1:])
    return jax.tree.map(split, batch)


def _int8_ef_transport(grads, opt_state, axis_name, block):
    """Per-leaf int8+error-feedback reduction; residual lives in opt_state."""
    flat_g, treedef = jax.tree.flatten(grads)
    flat_e = treedef.flatten_up_to(opt_state["ef"])
    out = [collectives.compressed_psum(g, axis_name, e, block=block)
           for g, e in zip(flat_g, flat_e)]
    new_grads = treedef.unflatten([o[0] for o in out])
    new_ef = treedef.unflatten([o[1] for o in out])
    return new_grads, {**opt_state, "ef": new_ef}


def make_train_step(cfg: ModelConfig, adamw: opt_lib.AdamWConfig,
                    microbatches: int = 1, grad_transport: str = "bf16",
                    mesh=None, data_axis: str = "data", ef_block: int = 256):
    """Returns train_step(params, opt_state, batch) -> (params, opt, metrics).

    Gradient accumulation runs as a ``lax.scan`` over microbatches; gradients
    are accumulated in fp32 and averaged.

    ``grad_transport`` picks how the gradient crosses the network:

    * ``"bf16"`` — the baseline. With FSDP/ZeRO rules the reduction crosses
      in bf16 (network dtype) while the AdamW math is fp32 on the shard.
    * ``"int8_ef"`` — blockwise int8 quantization with error feedback
      (``repro.dist.collectives.compressed_psum``); the per-leaf residual is
      carried in optimizer state under ``opt_state["ef"]``, so build the
      state with ``opt_lib.init_state(params, error_feedback=True)``.

    Two execution modes:

    * ``mesh=None`` (default) — the SPMD step the dry-run lowers: XLA owns
      the collectives, so int8_ef applies quantize→dequantize+EF to the
      already-reduced gradient (compression *error* and residual carry are
      exact; the wire stays XLA's).
    * ``mesh=<jax Mesh>`` — an explicit data-parallel step wrapped in
      ``shard_map`` over ``data_axis`` (the cross-pod role): params and
      moments replicated, the batch split, and the gradient reduction done
      manually — bf16 ``psum`` vs the two-stage int8 exchange — so the
      compiled HLO moves exactly the transport's bytes. This is the path
      the forced-8-device mesh tests compile, execute, and measure.
      ``opt_state["ef"]`` is per-device here: build it with
      ``init_state(params, error_feedback=True, ef_devices=W)``.
    """
    if grad_transport not in GRAD_TRANSPORTS:
        raise ValueError(f"unknown grad_transport {grad_transport!r}; "
                         f"expected one of {GRAD_TRANSPORTS}")
    loss_fn = make_loss_fn(cfg)
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def grads_and_metrics(params, batch):
        if microbatches > 1:
            mb = _split_microbatches(batch, microbatches)

            def accum(carry, mb_batch):
                gacc, lacc = carry
                (loss, metrics), grads = grad_fn(params, mb_batch)
                gacc = jax.tree.map(
                    lambda a, g: a + g.astype(jnp.float32), gacc, grads)
                return (gacc, lacc + loss), metrics

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (gsum, lsum), metrics_stack = jax.lax.scan(accum, (g0, 0.0), mb)
            grads = jax.tree.map(lambda g: g / microbatches, gsum)
            if grad_transport == "bf16":
                grads = jax.tree.map(lambda g: g.astype(jnp.bfloat16), grads)
            metrics = jax.tree.map(lambda m: m[-1], metrics_stack)
            metrics["loss"] = lsum / microbatches
        else:
            (loss, metrics), grads = grad_fn(params, batch)
        return grads, metrics

    def train_step(params, opt_state, batch):
        grads, metrics = grads_and_metrics(params, batch)
        if grad_transport == "int8_ef":
            grads, opt_state = _int8_ef_transport(grads, opt_state, None,
                                                  ef_block)
        new_params, new_opt, opt_metrics = opt_lib.apply_updates(
            adamw, params, grads, opt_state)
        metrics.update(opt_metrics)
        return new_params, new_opt, metrics

    if mesh is None:
        return train_step
    return _data_parallel_step(grads_and_metrics, adamw, mesh, data_axis,
                               grad_transport, ef_block)


def _data_parallel_step(grads_and_metrics, adamw, mesh, data_axis,
                        grad_transport, ef_block):
    """shard_map DDP wrapper: batch split over ``data_axis``, params/moments
    replicated, the gradient reduction explicit (and therefore measurable)."""
    from jax.sharding import PartitionSpec as P

    w = mesh.shape[data_axis]

    def device_step(params, opt_state, batch):
        grads, metrics = grads_and_metrics(params, batch)
        # each device holds d(mean local loss); global grad = psum(local)/W
        grads = jax.tree.map(lambda g: g.astype(jnp.float32) / w, grads)
        if grad_transport == "bf16":
            grads = jax.tree.map(
                lambda g: jax.lax.psum(g.astype(jnp.bfloat16), data_axis),
                grads)
        else:
            local = {**opt_state,
                     "ef": jax.tree.map(lambda e: e[0], opt_state["ef"])}
            grads, local = _int8_ef_transport(grads, local, data_axis,
                                              ef_block)
            opt_state = {**opt_state,
                         "ef": jax.tree.map(lambda e: e[None], local["ef"])}
        metrics = jax.tree.map(lambda m: jax.lax.pmean(m, data_axis), metrics)
        new_params, new_opt, opt_metrics = opt_lib.apply_updates(
            adamw, params, grads, opt_state)
        metrics.update(opt_metrics)
        return new_params, new_opt, metrics

    def opt_spec(with_ef):
        spec = {"mu": P(), "nu": P(), "step": P()}
        if with_ef:
            spec["ef"] = P(data_axis)   # per-device residual, leading axis
        return spec

    ospec = opt_spec(grad_transport == "int8_ef")
    return jax.shard_map(device_step, mesh=mesh,
                         in_specs=(P(), ospec, P(data_axis)),
                         out_specs=(P(), ospec, P()),
                         check_vma=False)


def _check_act_transport(act_transport: Optional[str]) -> None:
    if act_transport is not None and act_transport not in ACT_TRANSPORTS:
        raise ValueError(f"unknown act_transport {act_transport!r}; "
                         f"expected one of {ACT_TRANSPORTS}")


def make_encode_step(cfg: ModelConfig, act_transport: Optional[str] = "bf16"):
    """Encoder-only serving: full-sequence unit logits (HuBERT-style)."""
    _check_act_transport(act_transport)

    def encode_step(params, batch):
        with collectives.act_transport_scope(act_transport):
            logits, _ = transformer.forward(cfg, params, batch, "encode")
        return logits
    return encode_step


def make_prefill_step(cfg: ModelConfig, act_transport: Optional[str] = "bf16"):
    """Returns prefill_step(params, batch) -> (last-position logits, cache).

    ``batch`` may carry ``"last_pos"`` (per-row index of the final prompt
    token) for ragged continuous batching; without it the logits come from
    the last sequence position of every row.

    ``act_transport`` picks how the sequence-parallel activation all-gather
    (the ``sp``/``serve_sp`` residual-stream gather before attention and
    the MLP) crosses the wire: ``"bf16"`` reshards the raw payload,
    ``"int8"`` moves blockwise-int8 chunks + scales
    (``collectives.all_gather_int8``). No error feedback: activations are
    stateless across steps, so per-step quantization error never compounds.
    ``None`` disables the serve gather boundary entirely (legacy layout).
    """
    _check_act_transport(act_transport)

    def prefill_step(params, batch):
        with collectives.act_transport_scope(act_transport):
            logits, cache = transformer.forward(cfg, params, batch, "prefill")
        return logits, cache
    return prefill_step


def make_decode_step(cfg: ModelConfig, cache_len_total: int,
                     act_transport: Optional[str] = "bf16",
                     kv_storage: str = "bf16"):
    """Returns decode_step(params, cache, batch) -> (logits, new_cache).

    ``batch["pos"]`` is a scalar position or a per-row ``(B,)`` vector
    (ragged continuous batching). Under the ``serve_sp`` preset the KV
    cache is sharded over data (batch) x model (sequence); decode's
    activation all-gather is the cache gather feeding single-token
    attention, and ``act_transport="int8"`` runs it as blockwise-int8
    chunks + scales (see :func:`make_prefill_step`).

    ``kv_storage="int8"`` makes the cache int8-*resident*: the step
    expects (and emits) the storage layout from
    ``transformer.abstract_cache(..., kv_storage="int8")`` — s8 value
    leaves plus f32 ``<leaf>_scale`` leaves — writes each new token
    quantized per position, and attention dequantizes per block at read
    time. ``"f8"`` stores scale-free e4m3 leaves instead (same shapes as
    bf16, half the bytes, upcast per block at read time). Orthogonal to
    ``act_transport`` (storage is what HBM holds; the transport is how a
    reshard crosses the wire).
    """
    _check_act_transport(act_transport)
    if kv_storage not in KV_STORAGES:
        raise ValueError(f"unknown kv_storage {kv_storage!r}; "
                         f"expected one of {KV_STORAGES}")
    if kv_storage != "bf16":
        model_registry.require(cfg, "quantized_storage",
                               f"kv_storage={kv_storage!r}")

    def decode_step(params, cache, batch, counters=None):
        with collectives.act_transport_scope(act_transport), \
                collectives.kv_storage_scope(kv_storage):
            if counters is None:
                logits, new_cache = transformer.forward(
                    cfg, params, batch, "decode", cache=cache,
                    cache_len_total=cache_len_total)
                return logits, new_cache
            logits, new_cache, aux = transformer.forward(
                cfg, params, batch, "decode", cache=cache,
                cache_len_total=cache_len_total, return_aux=True)
        return logits, new_cache, moe.add_counters(counters, aux)
    return decode_step


def decode_counters(cfg: ModelConfig):
    """Zeroed device-side counters a decode step of ``cfg`` accumulates
    when passed them (``decode_step(params, cache, batch, counters)``),
    or ``None`` where the stack counts nothing: the held-share expert
    layer's pairs routed onto held experts (summed over layers and steps)
    and the most tokens one held expert took in one step."""
    if cfg.family != "mla_moe":
        return None
    return {k: jnp.zeros((), jnp.int32) for k in moe.HELD_COUNTERS}



def step_for_shape(cfg: ModelConfig, shape: ShapeSpec,
                   adamw: Optional[opt_lib.AdamWConfig] = None,
                   grad_transport: str = "bf16",
                   act_transport: str = "bf16",
                   kv_storage: str = "bf16"):
    """The function the dry-run lowers for a given cell, plus its kind."""
    if shape.kind == "train":
        return make_train_step(cfg, adamw or opt_lib.AdamWConfig(),
                               microbatches=shape.microbatches,
                               grad_transport=grad_transport), "train"
    if shape.kind == "prefill":
        if not cfg.supports_decode:      # encoder: no cache semantics
            return make_encode_step(cfg, act_transport), "encode"
        return make_prefill_step(cfg, act_transport), "prefill"
    return make_decode_step(cfg, shape.seq_len, act_transport,
                            kv_storage), "decode"
