"""Serving launcher: mesh-placed batched prefill + decode with a sharded
KV cache, quantized activation collectives, and optional prefill/decode
disaggregation.

``python -m repro.launch.serve --arch paper-lm-100m`` runs a batched
generation loop with the reduced smoke config (``--full`` lowers the real
published config instead) on a local mesh built over whatever devices exist
(1 CPU device degrades to a (1, 1) mesh; the CI multidevice job forces 8
host devices and gets a real (data, model) mesh). Params, KV cache, and
batch are explicitly placed: the ``serve_sp`` preset shards the cache over
data (batch dim) x model (sequence dim) and the residual stream over
sequence, and ``--act-transport int8`` runs the sequence-parallel
activation all-gathers as blockwise-int8 chunks + scales
(``repro.dist.collectives.act_gather``).

``--disagg`` splits the pipeline across two meshes — AutoComp's dedicated
compaction cluster, translated to serving: compute-bound prefill runs
sequence-parallel (``serve_sp``) on one half of the devices, decode runs
batch-heavy (``serve_decode``: cache resident, no per-step cache
collectives) on the other half, and the KV cache is handed off between
them once per request batch. ``--cache-transfer int8`` quantizes that
handoff blockwise along the sequence axis (s8 chunks + f32 scales on the
wire); ``--kv-storage {int8,f8}`` additionally keeps the decode-resident
cache quantized (~half the HBM: s8 + scales, or scale-free e4m3),
dequantized/upcast per block at attention read time. The knobs are
orthogonal — transfer x storage combinations, reported per decode dryrun
cell (``repro.launch.dryrun --shape decode``).

``--stream slots`` makes the handoff *continuous* (AutoComp's core lesson:
consolidation work runs concurrently with the serving it feeds, not as
stop-the-world batches): instead of prefilling a whole batch and handing
the cache to a fresh decode batch, each finished request's cache slice is
quantized/shipped/dequantized into a free row of a RUNNING decode batch
(slot admission), and the next slice's wire transfer is double-buffered
behind the current decode steps. Slots free as requests finish and are
reused by pending requests; greedy tokens are identical to the whole-batch
path.

Continuous batching: requests at different positions share one decode step
(``prompt_lens`` gives per-row lengths; positions/masks are per-row, so
padded prompt slots are never attended — same semantics the decode_attn
Pallas kernel implements on TPU).

There is one slot engine (:func:`_generate_slots`); its admission order is
owned by :class:`repro.dist.fanin.AdmissionArbiter`, and ``--workers``,
``--evict`` and ``--paged`` are its configuration. The plain slot stream
is one prefill worker, one priority class and no eviction: FIFO into the
lowest free slot with one shipment prefetched. ``--workers N`` (or
``--paged``) makes it the *fan-in* configuration: N independent prefill
workers — each running the same double-buffered mover — feed the ONE
decode slot table through the arbiter (FIFO with priority classes, aging
+ hard promotion mirroring the fleet scheduler's starvation bound,
per-worker in-flight accounting, and a deterministic tie-break: the
engine blocks on the arbiter's chosen shipment instead of racing worker
completion order, so admissions replay identically under permuted
arrival). When the table is full, ``--evict`` preempts a justified victim
— the evicted request requeues with its emitted tokens appended to its
prompt and is re-prefilled on readmission (recompute preemption; greedy
tokens bit-match an uncontended run). ``--paged`` swaps the dense
pad-to-horizon slot table for a *paged* one
(:class:`repro.models.registry.PagedStateStore`): rows are lists of
fixed-size pages in a shared pool with a per-slot page table, admission
ships only live pages, pages allocate on demand as a row decodes past a
page boundary, and the decode step runs *unchanged* on a dense view
gathered through the table (bit parity with the unpaged path). The page
size is a tunable axis on the kernel registry (``paged_attn``), swept by
``tune_design`` like every other kernel block. :class:`_Programs` builds
the prefill and decode programs, the parameter placements, the cache
mover and the zero cache, for the slot engine and the whole-batch body
alike. See docs/serving.md for the full operator's guide.
"""

from __future__ import annotations

import argparse
import contextlib
import time
from collections import deque
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, smoke_config
from repro.dist import collectives, fanin
from repro.dist import sharding as shd
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.models import registry, transformer
from repro.spans import (SERVE_ADMIT, SERVE_DECODE, SERVE_EMIT, SERVE_GENERATE,
                         SERVE_PREFILL, SERVE_SAMPLE, SERVE_SETUP,
                         SERVE_TRANSFER_WAIT, span)
from repro.train import step as step_lib


def grow_cache(cache, target):
    """Fit every cache leaf to the target shape: end-padding with zeros.

    ``target`` is the abstract decode cache, so windowed/SSM/xLSTM states
    are handled uniformly: leaves already at the target shape only cast,
    anything smaller pads with zeros at the end of each dimension (new
    slots read as empty and are masked by slot-position validity until
    written). A leaf *longer* than the target is first cut to it: a paged
    admission ships ``ceil(len / page)`` pages, which may be fewer
    positions than the ``[1, S0]`` prefill buffer (the dropped tail is
    pad junk beyond the request's live length, which per-row position
    masks never attend).
    """
    def grow(c, tgt):
        if c.shape == tgt.shape:
            return c.astype(tgt.dtype)
        if any(s > t for s, t in zip(c.shape, tgt.shape)):
            c = c[tuple(slice(0, t) for t in tgt.shape)]
        pad = [(0, t - s) for s, t in zip(c.shape, tgt.shape)]
        return jnp.pad(c, pad).astype(tgt.dtype)

    return jax.tree.map(grow, cache, target)


def make_cache_transfer_step(cfg, batch: int, total: int, mode: str,
                             block: int = collectives.ACT_BLOCK):
    """Single-mesh form of the prefill->decode cache handoff.

    Returns ``transfer(cache) -> cache`` that reshards every leaf to the
    layout the active ``axis_rules`` context resolves for its logical
    axes; ``mode="int8"`` routes leaves with a sequence axis through
    ``collectives.stream_int8`` (seq-blockwise s8 chunks + scales on the
    wire, ``block`` positions per chunk), everything else (recurrent
    state, ``mode="bf16"``) moves raw. jit it with in_shardings = the
    prefill layout and out_shardings = the decode layout under
    ``axis_rules(mesh, serve_decode)`` and the compiled HLO is the
    transfer's wire — what the dryrun and the disagg mesh tests measure.
    """
    if mode not in collectives.CACHE_TRANSFERS:
        raise ValueError(f"unknown cache_transfer {mode!r}; "
                         f"expected one of {collectives.CACHE_TRANSFERS}")
    axes = transformer.cache_axes(cfg, batch, total)

    def transfer(cache):
        def move(leaf, la):
            la = tuple(la)
            if mode == "int8" and "kv_seq" in la:
                return collectives.stream_int8(
                    leaf, *la, seq_axis=la.index("kv_seq"), block=block)
            return shd.constrain(leaf, *la)
        return jax.tree.map(move, cache, axes)
    return transfer


def make_cache_mover(cfg, batch: int, total: int, dec_mesh, dec_rules,
                     mode: str, dst_shardings):
    """Two-mesh cache handoff, built ONCE: returns ``move(cache) -> cache``
    placing a committed prefill cache (or a single request's ``batch=1``
    slice) onto the decode mesh. ``"bf16"`` is a plain ``device_put``;
    ``"int8"`` quantizes each sequence-carrying leaf blockwise along the
    sequence axis *on the prefill mesh*, moves the s8 chunks + f32 scales
    (the only cross-mesh traffic, ~1/4 the bf16 bytes), and dequantizes
    on arrival — AutoComp's compaction-output handoff, as a cache stream.
    The quantize/dequantize programs are jitted once here, so the slot
    streamer can call ``move`` per admission without recompiling.
    """
    if mode == "bf16":
        return lambda cache: jax.device_put(cache, dst_shardings)
    axes = transformer.cache_axes(cfg, batch, total)
    c_abs = transformer.abstract_cache(cfg, batch, total)
    abs_l, treedef = jax.tree.flatten(c_abs)
    axes_l = [tuple(a) for a in treedef.flatten_up_to(axes)]
    dst_l = treedef.flatten_up_to(dst_shardings)
    seq_ix = [la.index("kv_seq") if "kv_seq" in la else None for la in axes_l]
    dtypes = [x.dtype for x in abs_l]

    qs_shardings = []
    for x, si, la in zip(abs_l, seq_ix, axes_l):
        if si is None:
            qs_shardings.append(None)
            continue
        q_axes = la[:si] + la[si + 1:] + (la[si],)   # seq-last layout
        _, nb = collectives.lastdim_blocks(x.shape[si])
        s_shape = tuple(d for i, d in enumerate(x.shape) if i != si) + (nb,)
        qs_shardings.append((
            jax.sharding.NamedSharding(dec_mesh, shd.resolve_spec(
                x.shape[:si] + x.shape[si + 1:] + (x.shape[si],),
                q_axes, dec_mesh, dec_rules)),
            jax.sharding.NamedSharding(dec_mesh, shd.resolve_spec(
                s_shape, q_axes[:-1] + (None,), dec_mesh, dec_rules))))

    @jax.jit
    def quant(ls):                               # runs on the prefill mesh
        return [x if si is None
                else collectives.quantize_int8_seqaxis(x, si)
                for x, si in zip(ls, seq_ix)]

    def dequant(ls):
        return treedef.unflatten([
            x if si is None
            else collectives.dequantize_int8_seqaxis(x[0], x[1], si).astype(dt)
            for x, si, dt in zip(ls, seq_ix, dtypes)])
    dequant = jax.jit(dequant, out_shardings=dst_shardings)

    def move(cache):
        q_leaves = quant(jax.tree.leaves(cache))
        moved = []
        for x, si, dst, qs in zip(q_leaves, seq_ix, dst_l, qs_shardings):
            if si is None:
                moved.append(jax.device_put(x, dst))
            else:
                moved.append((jax.device_put(x[0], qs[0]),
                              jax.device_put(x[1], qs[1])))
        return dequant(moved)
    return move


class _Programs:
    """The serving programs of one ``generate`` call, built in one place
    for the whole-batch body and the slot engine.

    Resolves the meshes and rules (``rules`` defaults to ``serve_sp`` on
    ``mesh``; ``decode_mesh`` disaggregates, ``decode_rules`` defaulting
    to ``serve_decode``), places ``params`` once per distinct prefill mesh
    (``prefill_meshes``, one entry per prefill worker; default
    ``[mesh]``) and, disaggregated, once more on the decode mesh, and
    jits one ``prefill_step`` per distinct prefill mesh and one
    ``decode_step`` over a ``rows``-row cache of ``total`` positions in
    its ``kv_storage`` layout (pool-form through ``paged_store``'s page
    table when given), with the cache's decode-mesh shardings as its
    output layout. ``counters`` says whether the decode step is handed
    ``step_lib.decode_counters`` (and returns them). :meth:`grow`,
    :meth:`mover` and :meth:`init_cache` build the slice programs, the
    cross-mesh handoff and the zero cache on demand.
    """

    def __init__(self, cfg, params, rows: int, total: int, *, mesh, rules,
                 act_transport: str, decode_mesh, decode_rules,
                 cache_transfer: str, kv_storage: str,
                 prefill_meshes=None, paged_store=None,
                 counters: bool = False):
        if cache_transfer not in collectives.CACHE_TRANSFERS:
            raise ValueError(f"unknown cache_transfer {cache_transfer!r}; "
                             f"expected one of {collectives.CACHE_TRANSFERS}")
        meshes = [mesh] if prefill_meshes is None else list(prefill_meshes)
        if mesh is None:
            mesh = meshes[0]
        self.disagg = decode_mesh is not None
        if self.disagg and mesh is None:
            raise ValueError("disaggregated serving (decode_mesh=...) needs "
                             "a prefill mesh too")
        if mesh is not None and rules is None:
            rules = shd.PRESETS["serve_sp"]
        if self.disagg and decode_rules is None:
            decode_rules = shd.PRESETS["serve_decode"]
        self.cfg, self.cache_transfer = cfg, cache_transfer
        self.dec_mesh = decode_mesh if self.disagg else mesh
        self.dec_rules = decode_rules if self.disagg else rules
        # Under the serve_decode preset the cache is resident — decode has
        # no per-step gather to compress, so an int8 act transport there
        # would only round the whole resident cache through s8 every step
        # (logit drift, extra compute, zero wire saved). Drop to bf16 for
        # the decode half; custom decode_rules keep the caller's choice.
        dec_act = "bf16" if self.disagg and \
            self.dec_rules is shd.PRESETS["serve_decode"] else act_transport
        # validates kv_storage (and the family's eligibility for int8)
        decode_fn = step_lib.make_decode_step(cfg, total, dec_act,
                                              kv_storage)

        self.pre_ctx = [shd.axis_rules(m, rules) if m is not None
                        else contextlib.nullcontext() for m in meshes]
        self.dec_ctx = shd.axis_rules(self.dec_mesh, self.dec_rules) \
            if self.dec_mesh is not None else contextlib.nullcontext()

        def place(m, r):
            return params if m is None else jax.device_put(
                params, shd.tree_shardings(transformer.abstract_params(cfg),
                                           transformer.param_axes(cfg),
                                           m, r))
        # One prefill_step per DISTINCT prefill mesh: ``constrain`` bakes
        # the trace-time mesh into the jaxpr and jit reuses traces by
        # aval alone, so a shared jit would replay worker 0's sharding
        # constraints on worker 1's devices (incompatible-devices error on
        # the first cross-worker call). The trace cache is keyed on the
        # wrapped function object, so each mesh gets its own function
        # from make_prefill_step, not just its own jit wrapper.
        per_mesh = {}
        for m in meshes:
            if id(m) not in per_mesh:
                per_mesh[id(m)] = (place(m, rules), jax.jit(
                    step_lib.make_prefill_step(cfg, act_transport)))
        self.params_pre = [per_mesh[id(m)][0] for m in meshes]
        self.prefill = [per_mesh[id(m)][1] for m in meshes]
        # the decode cluster holds its own replica of the weights
        self.params_dec = place(self.dec_mesh, self.dec_rules) \
            if self.disagg else self.params_pre[0]

        if paged_store is not None:
            self.state_abs = paged_store.abstract_state()
            state_axes = paged_store.state_axes()
            decode_fn = _paged_decode_step(paged_store, decode_fn)
        else:
            self.state_abs = transformer.abstract_cache(
                cfg, rows, total, kv_storage=kv_storage)
            state_axes = transformer.cache_axes(cfg, rows, total,
                                                kv_storage=kv_storage)
        self.c_shard = None
        if self.dec_mesh is not None:
            self.c_shard = shd.tree_shardings(self.state_abs, state_axes,
                                              self.dec_mesh, self.dec_rules)
            out = (None, self.c_shard) + ((None,) if counters else ())
            self.decode = jax.jit(decode_fn, out_shardings=out)
        else:
            self.decode = jax.jit(decode_fn)
        self._grow, self._movers = {}, {}

    def grow(self, width: int):
        """Jitted :func:`grow_cache` of a one-request prefill cache to a
        ``[1, width]`` slice (one program per width; the device trace
        names it ``grow_cache``)."""
        if width not in self._grow:
            target = transformer.abstract_cache(self.cfg, 1, width)

            def grow(c):
                return grow_cache(c, target)
            grow.__name__ = "grow_cache"
            self._grow[width] = jax.jit(grow)
        return self._grow[width]

    def cache_shardings(self, rows: int, width: int):
        """The bf16 ``[rows, width]`` cache's shardings on the decode
        mesh."""
        return shd.tree_shardings(
            transformer.abstract_cache(self.cfg, rows, width),
            transformer.cache_axes(self.cfg, rows, width),
            self.dec_mesh, self.dec_rules)

    def mover(self, rows: int, width: int):
        """:func:`make_cache_mover` for a ``[rows, width]`` bf16 cache
        onto the decode mesh, built once per shape."""
        if (rows, width) not in self._movers:
            self._movers[rows, width] = make_cache_mover(
                self.cfg, rows, width, self.dec_mesh, self.dec_rules,
                self.cache_transfer, self.cache_shardings(rows, width))
        return self._movers[rows, width]

    def init_cache(self):
        """The zero cache in its decode-mesh layout."""
        state_abs = self.state_abs

        def init_cache():
            return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                                state_abs)
        return jax.jit(init_cache, out_shardings=self.c_shard)()


def _paged_decode_step(store, decode_fn):
    """The unchanged dense ``decode_fn`` bracketed by ``store``'s gather
    and scatter through the page table, which rides in the batch as
    ``"page_table"``."""
    def paged_decode_step(params, pool, batch, counters=None):
        batch = dict(batch)
        pt = batch.pop("page_table")
        logits, dense, *rest = decode_fn(
            params, store.gather_dense(pool, pt), batch, counters)
        return (logits, store.scatter_dense(pool, dense, pt), *rest)
    return paged_decode_step


STREAMS = ("batch", "slots")


def _default_page(base: int) -> int:
    """Page size when ``--page-size 0``: the tuned ``paged_attn`` registry
    point, capped so a row spans at least 8 pages — a near-single-page
    row degenerates to the dense pad-to-horizon layout and buys no HBM
    back, so small smoke horizons get proportionally small pages."""
    from repro.kernels.paged_attn import tuned_page_size
    return max(1, min(tuned_page_size(base), -(-base // 8)))


def _check_prompt_lens(cfg, lens: np.ndarray, b: int, s0: int,
                       max_new: int, total: int, paged: bool) -> None:
    """Loud validation of per-request lengths against the prompt buffer
    and the decode horizon.

    Bugfix: these used to be bare ``assert``s — stripped under ``-O``,
    and even when they fired they named nothing. A request longer than
    the decode horizon would silently truncate (its tail positions
    written past the cache end are dropped by the update's clamp) and
    serve wrong tokens without a word. Refuse loudly instead, in the
    same uniform style as ``registry.require``; under ``--paged`` the
    horizon cap does not apply (pages allocate on demand), so the same
    request admits.
    """
    lens = np.asarray(lens)
    if lens.shape != (b,):
        raise ValueError(f"prompt_lens shape {tuple(lens.shape)} does not "
                         f"match the batch ({b},)")
    if (lens < 1).any():
        raise ValueError("every request needs at least one prompt token; "
                         f"got prompt_lens={lens.tolist()}")
    over = np.nonzero(lens > s0)[0]
    if over.size:
        i = int(over[0])
        raise ValueError(
            f"request {i} claims {int(lens[i])} prompt tokens but the "
            f"prompt buffer holds only {s0}: the overflow was already "
            f"lost — refusing to serve a silently truncated prompt")
    if paged:
        return
    over = np.nonzero(lens + max_new > total)[0]
    if over.size:
        i = int(over[0])
        raise ValueError(
            f"request {i} needs {int(lens[i]) + max_new} positions "
            f"(prompt {int(lens[i])} + {max_new} new) but the decode "
            f"horizon is {total} for {cfg.name}: refusing to truncate — "
            f"raise --horizon, or serve --paged (pages allocate on "
            f"demand, so long requests admit instead of truncating)")


def generate(cfg, params, prompts: np.ndarray, max_new: int = 16,
             temperature: float = 0.0, seed: int = 0,
             prompt_lens: Optional[np.ndarray] = None,
             mesh=None, rules=None, act_transport: str = "bf16",
             decode_mesh=None, decode_rules=None,
             cache_transfer: str = "bf16", kv_storage: str = "bf16",
             stream: str = "batch", slots: int = 0,
             workers: int = 1, evict: str = "oldest", paged: bool = False,
             page_size: int = 0, pool_pages: int = 0, horizon: int = 0,
             priorities: Optional[np.ndarray] = None, prefill_meshes=None,
             keep_logits: bool = False):
    """prompts: (B, S0) int32, right-padded when ragged. Greedy (or
    sampled) decode of ``max_new`` tokens per row.

    ``prompt_lens`` (B,) enables ragged continuous batching: row i's real
    prompt is ``prompts[i, :prompt_lens[i]]``; every row decodes from its
    own position and pad slots are masked (each row's output matches a
    solo run of its unpadded prompt). ``mesh`` places params/cache/batch
    explicitly (``rules`` defaults to the ``serve_sp`` preset);
    ``act_transport`` picks the activation all-gather wire format.

    ``decode_mesh`` disaggregates: prefill compiles on ``mesh`` (its own
    devices, ``rules``), decode on ``decode_mesh`` (``decode_rules``,
    default the batch-heavy ``serve_decode`` preset), and the prefilled
    cache crosses between them — raw under ``cache_transfer="bf16"``, as
    seq-blockwise s8 chunks + scales under ``"int8"``.
    ``kv_storage="int8"`` keeps the decode-resident cache int8 (works
    colocated too, and even without a mesh); ``"f8"`` stores scale-free
    e4m3 instead (same HBM saving, no scale leaves).

    ``stream`` picks the handoff granularity: ``"batch"`` (this function's
    body) prefills the whole batch and hands the cache to a fresh decode
    batch once; ``"slots"`` streams each request's cache slice into a
    *running* decode batch via slot admission (``slots`` = slot-table
    size, 0 = one per request) with the next slice's wire transfer
    double-buffered behind the current decode steps — see
    :func:`_generate_slots`.

    ``workers > 1``, ``paged=True`` or ``prefill_meshes`` serves through
    the slot engine's fan-in configuration, whatever ``stream`` says:
    ``workers`` prefill workers (optionally on their own meshes via
    ``prefill_meshes``) feed the slot table through the admission
    arbiter; ``evict`` picks the preemption policy, ``priorities`` (B,)
    assigns admission classes (0 = most urgent), and
    ``paged``/``page_size``/``pool_pages`` swap in the paged slot cache.
    ``horizon`` caps the decode horizon in positions (0 = sized to fit):
    an unpaged request that cannot fit is refused loudly, never silently
    truncated; a paged one admits.

    ``keep_logits=True`` (the batch stream and the plain slot stream)
    keeps the logit row each generated token was chosen from, on the
    device until the call returns, and leaves them as
    ``last_stats["logits"]``, float32 ``(B, max_new, vocab)``: what a
    caller checks the serving numerics against. Off, the loop keeps
    nothing.
    """
    if stream not in STREAMS:
        raise ValueError(f"unknown stream {stream!r}; "
                         f"expected one of {STREAMS}")
    if stream == "slots" or workers > 1 or paged or \
            prefill_meshes is not None:
        return _generate_slots(
            cfg, params, prompts, max_new=max_new, temperature=temperature,
            seed=seed, prompt_lens=prompt_lens, mesh=mesh, rules=rules,
            act_transport=act_transport, decode_mesh=decode_mesh,
            decode_rules=decode_rules, cache_transfer=cache_transfer,
            kv_storage=kv_storage, slots=slots, horizon=horizon,
            keep_logits=keep_logits, workers=workers, evict=evict,
            paged=paged, page_size=page_size, pool_pages=pool_pages,
            priorities=priorities, prefill_meshes=prefill_meshes)
    b, s0 = prompts.shape
    total = s0 + max_new
    ragged = prompt_lens is not None
    lens = np.asarray(prompt_lens, np.int32) if ragged else None
    _check_prompt_lens(cfg, lens if ragged else np.full((b,), s0, np.int32),
                       b, s0, max_new, int(horizon) or total, paged=False)
    if ragged:
        # Ragged masking is only sound for full (slot == position) caches:
        # ring buffers alias a padded position's junk slot to an in-window
        # position before the row overwrites it, and SSM/xLSTM recurrent
        # states scan pad tokens in during prefill — per-row masks cannot
        # undo either. row_state families serve mixed lengths through
        # --stream slots (exact-length per-request prefill) instead.
        registry.require(cfg, "ragged", "ragged prompt_lens")
    progs = _Programs(cfg, params, b, total, mesh=mesh, rules=rules,
                      act_transport=act_transport, decode_mesh=decode_mesh,
                      decode_rules=decode_rules,
                      cache_transfer=cache_transfer, kv_storage=kv_storage)

    with progs.pre_ctx[0]:
        pre_batch = {"tokens": jnp.asarray(prompts)}
        if ragged:
            pre_batch["last_pos"] = jnp.asarray(lens - 1)
        logits, cache = progs.prefill[0](progs.params_pre[0], pre_batch)
        cache = grow_cache(cache, transformer.abstract_cache(cfg, b, total))

    # ---- handoff: place the grown cache on the decode side
    with progs.dec_ctx:
        if progs.disagg:
            cache = progs.mover(b, total)(cache)
        elif progs.dec_mesh is not None:
            # colocated: commit the grown cache to its serve placement
            cache = jax.device_put(cache, progs.cache_shardings(b, total))
        if kv_storage != "bf16":
            quant = jax.jit(lambda c: transformer.quantize_cache(
                c, kv_storage), out_shardings=progs.c_shard)
            cache = quant(cache)

        # first sampled token comes from prefill logits — the one batch
        # tensor that crosses from the prefill to the decode mesh
        key = jax.random.PRNGKey(seed)
        out_tokens = []
        kept = []
        tok = jnp.asarray(np.asarray(jnp.argmax(logits, -1),
                                     dtype=np.int32)[:, None])
        for i in range(max_new):
            out_tokens.append(np.asarray(tok))
            if keep_logits:
                kept.append(logits)
            pos = jnp.asarray(lens + i) if ragged \
                else jnp.asarray(s0 + i, jnp.int32)
            logits, cache = progs.decode(progs.params_dec, cache,
                                         {"tokens": tok, "pos": pos})
            if temperature > 0:
                key, sub = jax.random.split(key)
                tok = jax.random.categorical(sub, logits / temperature
                                             ).astype(jnp.int32)[:, None]
            else:
                tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
    generate.last_stats = {"logits": np.stack(
        [np.asarray(x, np.float32) for x in kept], axis=1)
        if keep_logits else None}
    return np.concatenate(out_tokens, axis=1)


def supports_slot_streaming(cfg) -> bool:
    """Every family serves through slot streaming now that admission is a
    StateStore row write: attention caches admit as ``[1, total]`` cache
    slices, ring-buffer and recurrent (``row_state``) families admit
    their O(1) per-row state as a whole-row overwrite after an
    exact-length prefill."""
    return registry.capabilities(cfg).slot_stream


def _require_slot_streaming(cfg) -> None:
    registry.require(cfg, "slot_stream", "--stream slots")


def make_slot_admit_step(cfg, slots: int, total: int, transfer: str,
                         kv_storage: str,
                         block: int = collectives.ACT_BLOCK):
    """Admission step of continuous slot streaming: returns
    ``admit(cache, slice, slot) -> cache`` — a thin wrapper over
    :meth:`repro.models.registry.StateStore.admit_row`, writing one
    request's grown ``[1, total]`` bf16 state slice into row ``slot`` of
    the *running* decode state table (in its resident storage layout).
    ``slot`` is a traced scalar, so one compiled program serves every
    slot.

    ``transfer`` is the colocated wire form: ``"int8"`` routes each
    sequence-carrying leaf through ``collectives.stream_slot_int8`` and
    each O(1) row-state leaf through ``collectives.stream_row_int8``, so
    the compiled slice reshard carries s8 chunks + f32 scales — the
    program the dryrun parses for per-slot wire bytes. The two-mesh
    launcher ships the slice with ``make_cache_mover`` *before* admission
    and calls this with ``transfer="bf16"``.
    """
    if transfer not in collectives.CACHE_TRANSFERS:
        raise ValueError(f"unknown cache_transfer {transfer!r}; "
                         f"expected one of {collectives.CACHE_TRANSFERS}")
    _require_slot_streaming(cfg)
    store = registry.state_store(cfg, slots, total, kv_storage=kv_storage)

    def admit(cache, slc, slot):
        return store.admit_row(cache, slc, slot, transfer=transfer,
                               block=block)
    return admit


def _generate_slots(cfg, params, prompts: np.ndarray, max_new: int,
                    temperature: float, seed: int,
                    prompt_lens: Optional[np.ndarray],
                    mesh, rules, act_transport: str,
                    decode_mesh, decode_rules,
                    cache_transfer: str, kv_storage: str, slots: int,
                    horizon: int = 0, keep_logits: bool = False,
                    workers: int = 1, evict: str = "none",
                    paged: bool = False, page_size: int = 0,
                    pool_pages: int = 0,
                    priorities: Optional[np.ndarray] = None,
                    prefill_meshes=None):
    """The slot engine: prefill streams each finished request's cache
    slice into a RUNNING decode batch.

    The decode side holds a slot table of ``slots`` rows (the state's
    batch dim doubles as the slot dim). Each request is prefilled on its
    own — ``[1, S0]`` with a per-row last position for dense caches,
    ``[1, len_i]`` exact-length for ``row_state`` families (ring buffers
    and recurrent scans must never see pad tokens) — its grown slice is
    quantized/shipped/dequantized into a free slot
    (:func:`make_slot_admit_step`, a :class:`~repro.models.registry.\
StateStore` row write), and the slot decodes from the request's own
    position while other slots are mid-decode or still empty. A finished
    slot is freed and reused by the next pending request — admission
    overwrites the entire ``[1, total]`` row, so no state can bleed
    between consecutive occupants. Transfers are double-buffered: each
    prefill worker's next shipment is dispatched (async) at admission
    time, so it overlaps the decode steps that run before the next slot
    frees; the wall-clock wait the overlap failed to hide is recorded in
    ``_generate_slots.last_stats`` (the launcher prints it), with the
    kept logit rows under ``keep_logits`` (see :func:`generate`). A
    configuration whose decode step counts (``step_lib.decode_counters``:
    the held-share expert layer's ``moe_held_pairs`` and
    ``moe_max_expert_tokens``, over every row the steps compute)
    accumulates them on the device through the steps and reads them back
    once, into ``last_stats``.

    Admission order is owned by :class:`repro.dist.fanin.AdmissionArbiter`.
    The plain configuration (one worker, no ``paged``, no
    ``prefill_meshes``) is one class, one shipment in flight and no
    eviction: FIFO into the lowest free slot. The fan-in configuration
    (``workers > 1``, ``paged`` or ``prefill_meshes``) has ``workers``
    prefill workers, each on its own mesh when ``prefill_meshes`` gives
    one per worker, priority classes (``priorities``), aging + hard
    promotion and per-worker in-flight accounting; the engine *blocks on
    the arbiter's chosen shipment* rather than admitting whichever worker
    finishes first, so the token stream is replayable under permuted
    worker completion order. There, when the table is full and the
    pending request outranks a victim (or has hit the hard promotion
    bound), ``evict`` preempts it recompute-style: the victim's slot is
    freed, and the victim requeues with its already-emitted tokens
    appended to its prompt and ``max_new`` reduced by them. Readmission
    prefills the extended prompt at its exact length — the first
    readmitted token comes from the prefill's last-position logits — so
    the greedy continuation is bit-identical to an uncontended run (the
    parity ``tests/test_serve_fanin.py`` pins). The fan-in configuration
    is greedy-only (a sampled continuation across that recompute is not
    replayable) and keeps no logits.

    ``paged=True`` stores the slot table as a
    :class:`repro.models.registry.PagedStateStore`: admission allocates
    and ships only the pages covering the request's live positions, a
    fresh page is allocated (host-side) whenever a slot decodes across a
    page boundary, and each decode step runs the *unchanged* dense step
    bracketed by the store's gather/scatter through the page table —
    greedy tokens bit-match the unpaged path. The page size comes from
    the tuned ``paged_attn`` registry point unless ``page_size`` pins
    it; ``pool_pages`` bounds the shared pool (0 = fully backed), and
    exhausting it is a loud error, never a stall. Long requests that an
    unpaged horizon would refuse admit here — the horizon grows to the
    next page multiple that fits the longest request.

    Returns tokens ``(B, max_new)``; greedy tokens are token-for-token
    identical to the whole-batch path (per-row attention independence —
    the property ``tests/test_serve_disagg.py`` pins on the 8-device
    mesh).
    """
    fan_in = workers > 1 or paged or prefill_meshes is not None
    if fan_in:
        if keep_logits:
            raise ValueError("keep_logits: the batch stream and the plain "
                             "slot stream only (not workers > 1, paged "
                             "or prefill_meshes)")
        if temperature > 0:
            raise ValueError(
                "fan-in serving is greedy-only: an evicted request "
                "re-prefills its emitted tokens on readmission, and a "
                "sampled continuation across that recompute is not "
                "replayable; use temperature=0 (the single-worker paths "
                "support sampling)")
        if evict not in fanin.EVICTION_POLICIES:
            raise ValueError(f"unknown eviction policy {evict!r}; "
                             f"expected one of {fanin.EVICTION_POLICIES}")
        if workers < 1:
            raise ValueError(
                f"need at least one prefill worker, got {workers}")
    else:
        workers, evict, priorities = 1, "none", None
    b, s0 = prompts.shape
    lens = np.asarray(prompt_lens, np.int32) if prompt_lens is not None \
        else np.full((b,), s0, np.int32)
    if paged:
        # the horizon never caps a paged table — it grows to the longest
        # request (a request an unpaged horizon refuses admits here)
        base = max(int(horizon), int((lens + max_new).max()))
        P = int(page_size) or _default_page(base)
        if P < 1:
            raise ValueError(f"page size must be >= 1, got {P}")
        total = -(-base // P) * P        # next page multiple that fits
    else:
        P = 0
        total = int(horizon) if horizon else s0 + max_new
    _check_prompt_lens(cfg, lens, b, s0, max_new, total, paged=paged)
    # fail before any compile: quantized storage refuses recurrent
    # caches; make_slot_admit_step re-checks for direct callers
    _require_slot_streaming(cfg)
    caps = registry.capabilities(cfg)
    prios = np.zeros((b,), np.int32) if priorities is None \
        else np.asarray(priorities, np.int32)
    if prios.shape != (b,):
        raise ValueError(f"priorities shape {tuple(prios.shape)} does not "
                         f"match the batch ({b},)")
    n_slots = int(slots) if slots else b
    if n_slots < 1:
        raise ValueError(f"slot table needs at least one slot, got {slots}")
    if prefill_meshes is not None:
        prefill_meshes = list(prefill_meshes)
        if len(prefill_meshes) != workers:
            raise ValueError(
                f"{len(prefill_meshes)} prefill meshes for {workers} "
                f"workers: fan-in needs one mesh per worker (or none)")
    else:
        prefill_meshes = [mesh] * workers

    with span(SERVE_GENERATE, requests=b, slots=n_slots):
        with span(SERVE_SETUP):
            store = registry.paged_state_store(
                cfg, n_slots, total, kv_storage=kv_storage, page=P,
                pool_pages=int(pool_pages)) if paged else None
            # device-side counters the decode steps accumulate (the
            # held-share expert layer's), read back once at the end
            counters = step_lib.decode_counters(cfg)
            progs = _Programs(
                cfg, params, n_slots, total, mesh=mesh, rules=rules,
                act_transport=act_transport, decode_mesh=decode_mesh,
                decode_rules=decode_rules, cache_transfer=cache_transfer,
                kv_storage=kv_storage, prefill_meshes=prefill_meshes,
                paged_store=store, counters=counters is not None)
            admit_transfer = "bf16" if progs.disagg else cache_transfer
            if paged:
                def admit_pages(cache, slc, page_idx):
                    return store.admit_pages(cache, slc, page_idx,
                                             transfer=admit_transfer)
                admit = jax.jit(admit_pages, out_shardings=progs.c_shard)
            else:
                admit = jax.jit(make_slot_admit_step(
                    cfg, n_slots, total, admit_transfer, kv_storage),
                    out_shardings=progs.c_shard)
            with progs.dec_ctx:
                cache = progs.init_cache()

        # ---- host side: arbiter queue, slot table, page table -----------
        key = jax.random.PRNGKey(seed)
        arb = fanin.AdmissionArbiter(
            workers=workers, classes=int(prios.max()) + 1 if b else 1)
        prompt_of = [np.asarray(prompts[i, :lens[i]], np.int32)
                     for i in range(b)]
        reqs = [arb.submit(fanin.Request(rid=i, prompt=prompt_of[i],
                                         max_new=int(max_new),
                                         priority=int(prios[i])))
                for i in range(b)]
        out_tokens = [[] for _ in range(b)]
        slot_req = [-1] * n_slots          # request id per slot, -1 = free
        slot_occ: list = [None] * n_slots  # the arbiter's fanin.Occupant
        slot_tok = np.zeros((n_slots,), np.int32)
        slot_pos = np.zeros((n_slots,), np.int32)
        slot_keys: list = [None] * n_slots
        # rid -> (cache slice, first token, its logits, prompt length)
        shipments = {}
        pt = store.init_page_table() if paged else None
        free_pages = deque(range(store.n_pool)) if paged else None
        stats = {"admissions": 0, "evictions": 0, "requeues": 0,
                 "decode_steps": 0, "transfer_wait_s": 0.0,
                 "max_wait_passes": 0}
        if paged:
            stats["peak_live_pages"] = 0
        # per request, the (logits, row) each of its tokens was chosen from
        kept = [[] for _ in range(b)] if keep_logits else None

        def alloc_page() -> int:
            if not free_pages:
                raise RuntimeError(
                    f"paged pool exhausted: all {store.n_pool} pages of "
                    f"the {n_slots}-slot table are live; raise "
                    f"--pool-pages (0 = fully backed: slots x "
                    f"pages-per-row = {n_slots * store.pages_per_row}) or "
                    f"lower --slots")
            p = free_pages.popleft()
            stats["peak_live_pages"] = max(stats["peak_live_pages"],
                                           store.n_pool - len(free_pages))
            return p

        def ensure_page(s, pos):
            """Allocate the page holding ``pos`` before the slot writes
            it."""
            pg = pos // P
            if pg >= store.pages_per_row:
                raise RuntimeError(
                    f"slot {s} at position {pos} is past the {total}-"
                    f"position paged horizon — engine accounting bug")
            if pt[s, pg] < 0:
                pt[s, pg] = alloc_page()

        def free_row(s):
            if paged:
                for pg in np.nonzero(pt[s] >= 0)[0]:
                    free_pages.append(int(pt[s, pg]))
                pt[s, :] = -1
            slot_req[s] = -1
            slot_occ[s] = None

        def dispatch():
            """Prefill + ship the requests the arbiter hands to free
            workers (async dispatch): each wire transfer overlaps whatever
            decode steps run before its admission — the double buffer."""
            for req in arb.assign():
                i, w = req.rid, req.worker
                plen = int(req.prompt.shape[0])
                with span(SERVE_PREFILL, request=i):
                    with progs.pre_ctx[w]:
                        if caps.row_state or req.evictions:
                            # ring-buffer / recurrent state: pad tokens
                            # must never enter the per-row state, and a
                            # readmission replays its emitted tokens, so
                            # prefill at the exact length (one compile
                            # per distinct length)
                            logits, c = progs.prefill[w](
                                progs.params_pre[w],
                                {"tokens": jnp.asarray(req.prompt[None])})
                        else:
                            logits, c = progs.prefill[w](
                                progs.params_pre[w], {
                                    "tokens": jnp.asarray(prompts[i:i + 1]),
                                    "last_pos": jnp.asarray(
                                        lens[i:i + 1] - 1)})
                        width = -(-plen // P) * P if paged else total
                        slc = progs.grow(width)(c)
                        tok0 = jnp.argmax(logits, -1).astype(jnp.int32)
                    if progs.disagg:
                        slc = progs.mover(1, width)(slc)
                    shipments[i] = (slc, tok0, logits, plen)

        def emit(i, t, slot):
            out_tokens[i].append(int(t))
            if len(out_tokens[i]) >= max_new:
                free_row(slot)             # free the slot for reuse

        def evict_slot(s):
            i = slot_req[s]
            arb.evicted(reqs[i])
            # recompute preemption: requeue with the emitted tokens
            # appended, budget reduced by them; aging restarts
            reqs[i].prompt = np.concatenate(
                [prompt_of[i], np.asarray(out_tokens[i], np.int32)])
            reqs[i].max_new = max_new - len(out_tokens[i])
            free_row(s)
            arb.submit(reqs[i], requeue=True)
            stats["evictions"] += 1
            stats["requeues"] += 1

        def admit_into(slot, req):
            nonlocal cache
            i = req.rid
            slc, tok0, logits0, plen = shipments.pop(i)
            with span(SERVE_ADMIT, request=i):
                with span(SERVE_TRANSFER_WAIT):
                    t0 = time.time()
                    # the arbiter's choice, not the first shipment done:
                    # what the overlap failed to hide
                    jax.block_until_ready(slc)
                    stats["transfer_wait_s"] += time.time() - t0
                slot_occ[slot] = arb.admit(req)
                stats["max_wait_passes"] = max(stats["max_wait_passes"],
                                               req.skips)
                with progs.dec_ctx:
                    if paged:
                        n_ship = -(-plen // P)
                        idx = np.asarray([alloc_page()
                                          for _ in range(n_ship)], np.int32)
                        pt[slot, :n_ship] = idx
                        cache = admit(cache, slc, jnp.asarray(idx))
                    else:
                        cache = admit(cache, slc,
                                      jnp.asarray(slot, jnp.int32))
                stats["admissions"] += 1
                slot_req[slot] = i
                slot_pos[slot] = plen
                slot_tok[slot] = int(np.asarray(tok0)[0])
                slot_keys[slot] = jax.random.fold_in(key, i)
                if kept is not None:
                    kept[i].append((logits0, 0))
                emit(i, slot_tok[slot], slot)  # the prefill token
                dispatch()                 # double buffer the next shipment

        def refill():
            """Admit in the arbiter's order until the queue or the table
            runs out — a slot freed AT admission (max_new == 1: the
            prefill token is the whole request) is refilled in the same
            pass."""
            dispatch()
            while True:
                req = arb.next_admission()
                if req is None:
                    return
                s = next((j for j in range(n_slots) if slot_req[j] < 0),
                         None)
                if s is None:
                    s = arb.pick_victim(slot_occ, evict, req)
                    if s is None:
                        return             # no justified victim: it ages
                    evict_slot(s)
                admit_into(s, req)

        # ---- main loop: admit -> age -> decode -> sample -> emit ---------
        passes = 0
        limit = 1000 + 20 * b * (max_new + n_slots + arb.promotion_cycles)
        while True:
            passes += 1
            if passes > limit:
                raise RuntimeError(
                    f"fan-in engine made no progress in {limit} passes "
                    f"(queue={len(arb.queue)}, "
                    f"occupied={sum(r >= 0 for r in slot_req)})")
            refill()
            arb.age()
            if all(r < 0 for r in slot_req):
                if not arb.queue:
                    break                  # nothing active, nothing pending
                continue
            with span(SERVE_DECODE, step=stats["decode_steps"]):
                batch = {"tokens": jnp.asarray(slot_tok[:, None]),
                         "pos": jnp.asarray(slot_pos)}
                if paged:
                    for s_ in range(n_slots):
                        if slot_req[s_] >= 0:
                            ensure_page(s_, int(slot_pos[s_]))
                    batch["page_table"] = jnp.asarray(pt)
                with progs.dec_ctx:
                    if counters is None:
                        logits, cache = progs.decode(progs.params_dec,
                                                     cache, batch)
                    else:
                        logits, cache, counters = progs.decode(
                            progs.params_dec, cache, batch, counters)
            stats["decode_steps"] += 1
            with span(SERVE_SAMPLE):
                if temperature > 0:
                    logits_np = np.asarray(logits, np.float32)
                    nxt = np.zeros((n_slots,), np.int32)
                    for s_ in range(n_slots):
                        if slot_req[s_] < 0:
                            continue
                        slot_keys[s_], sub = jax.random.split(slot_keys[s_])
                        nxt[s_] = int(jax.random.categorical(
                            sub, jnp.asarray(logits_np[s_]) / temperature))
                else:
                    nxt = np.asarray(jnp.argmax(logits, -1), np.int32)
            with span(SERVE_EMIT):
                for s_ in range(n_slots):
                    i = slot_req[s_]
                    if i < 0:
                        continue
                    slot_tok[s_] = nxt[s_]
                    slot_pos[s_] += 1
                    if kept is not None:
                        kept[i].append((logits, s_))
                    emit(i, nxt[s_], s_)

        bad = [i for i in range(b) if len(out_tokens[i]) != max_new]
        if bad:
            raise RuntimeError(f"fan-in engine dropped requests {bad}: "
                               f"emitted {[len(out_tokens[i]) for i in bad]} "
                               f"of {max_new} tokens")
        if counters is not None:
            stats.update({k: int(v) for k, v in
                          jax.device_get(counters).items()})
        if paged:
            stats["page"] = P
            stats["hbm_bytes_per_slot"] = (stats["peak_live_pages"]
                                           * store.page_bytes()) // n_slots
            dense = sum(int(np.prod(l.shape)) * l.dtype.itemsize
                        for l in store.dense_abstract_state().values())
            stats["dense_hbm_bytes_per_slot"] = dense // n_slots
        if kept is not None:
            host = {}                      # one device-to-host copy per array

            def row(x, r):
                if id(x) not in host:
                    host[id(x)] = np.asarray(x, np.float32)
                return host[id(x)][r]
            kept = np.stack([[row(x, r) for x, r in rows] for rows in kept])
        stats["logits"] = kept
        _generate_slots.last_stats = stats     # launcher reporting hook
        return np.asarray(out_tokens, np.int32)


def _pick_tp(n_devices: int, cfg) -> int:
    """Largest model-parallel degree (<= 2) the device count and head
    counts admit — the smoke default; override with --tp."""
    for tp in (2, 1):
        if n_devices % tp == 0 and cfg.n_heads % tp == 0:
            return tp
    return 1


def make_disagg_meshes(cfg, tp_prefill: int = 0, tp_decode: int = 0):
    """Split the local devices into a prefill mesh and a decode mesh.

    With >= 2 devices the halves are disjoint — two real clusters, the
    cache handoff is a genuine cross-mesh transfer. A single device serves
    both roles (degenerate (1, 1) meshes), so the smoke path runs
    anywhere. Each half keeps a (data, model) layout; ``tp_*=0``
    auto-picks the model degree per half.
    """
    devs = jax.devices()
    n = len(devs)
    pre, dec = (devs[:n // 2], devs[n // 2:]) if n >= 2 else (devs, devs)

    def mk(ds, tp):
        tp = tp or _pick_tp(len(ds), cfg)
        if len(ds) % tp != 0:
            raise ValueError(
                f"model-parallel degree {tp} does not divide the "
                f"{len(ds)}-device mesh half: disaggregated serving gives "
                f"each role {len(ds)} of the {n} devices, so --tp must "
                f"divide that")
        arr = np.array(ds).reshape(len(ds) // tp, tp)
        return jax.sharding.Mesh(arr, ("data", "model"))
    return mk(pre, tp_prefill), mk(dec, tp_decode)


def make_fanin_meshes(cfg, workers: int, tp_prefill: int = 0,
                      tp_decode: int = 0):
    """Split the local devices into ``workers`` prefill-worker meshes plus
    one decode mesh.

    The decode half mirrors :func:`make_disagg_meshes`; the prefill half
    is divided evenly among the workers (each an independent
    ``(data, model)`` mesh — N real prefill clusters) when its device
    count allows, and shared by every worker otherwise (the workers are
    then concurrency lanes on one mesh — degenerate, but it runs
    anywhere and still exercises the admission arbiter). Returns
    ``(prefill_meshes, decode_mesh)`` with ``len(prefill_meshes) ==
    workers``.
    """
    if workers < 1:
        raise ValueError(f"need at least one prefill worker, got {workers}")
    devs = jax.devices()
    n = len(devs)
    pre, dec = (devs[:n // 2], devs[n // 2:]) if n >= 2 else (devs, devs)
    if len(pre) >= workers and len(pre) % workers == 0:
        chunk = len(pre) // workers
        groups = [pre[w * chunk:(w + 1) * chunk] for w in range(workers)]
    else:
        groups = [list(pre)] * workers

    def mk(ds, tp):
        tp = tp or _pick_tp(len(ds), cfg)
        if len(ds) % tp != 0:
            raise ValueError(
                f"model-parallel degree {tp} does not divide the "
                f"{len(ds)}-device mesh: fan-in gives each of the "
                f"{workers} prefill workers {len(groups[0])} and decode "
                f"{len(dec)} of the {n} devices, so --tp must divide "
                f"those")
        arr = np.array(ds).reshape(len(ds) // tp, tp)
        return jax.sharding.Mesh(arr, ("data", "model"))
    return [mk(g, tp_prefill) for g in groups], mk(dec, tp_decode)


def disagg_decode_report(cfg, batch: int, seq_len: int, mesh,
                         ici_bw: float = 50e9, hbm_bw: float = 819e9,
                         transfers=collectives.CACHE_TRANSFERS,
                         storages=collectives.KV_STORAGES,
                         blocks=(collectives.ACT_BLOCK,)):
    """Compile the disaggregated-decode design space on one mesh and
    report every cache_transfer x kv_storage (x stream block) combination.

    Per combination ``"<transfer>x<storage>"``: ``transfer_s`` (the
    serve_sp -> serve_decode cache reshard's wire, HLO-parsed from the
    compiled transfer program), ``decode_step_s`` (the decode step's
    per-token wire under the storage arm), their sum ``collective_s``,
    ``cache_resident_bytes_per_device`` (what the decode mesh's HBM
    actually holds — the storage arm's rent), and
    ``slot_stream_overlap_frac``: the fraction of a *per-slot* transfer
    (one request's ``[1, seq]`` slice, HLO-parsed from the compiled slot
    admission program — ``rep["slot_stream"]``) a double-buffered
    admission hides behind decode steps, modeling the steady state where
    the slot table readmits one of its ``batch`` slots every
    ``seq_len/batch`` decode steps. Extra ``blocks`` sweep the stream's
    quantization block size (``rep["block_sweep"]``; f32 scales per
    block, so smaller blocks buy fidelity with wire), and
    ``rep["tuned"]`` is the ``repro.core.autotune.tune_design`` hillclimb
    over transfer x storage x block minimizing the combo's modeled cost:
    wire ``collective_s`` plus the per-token HBM read of the resident
    cache (``cache_resident_bytes / hbm_bw`` — what the storage arm
    actually buys back). Storage arms a family does not support (recurrent
    caches) are skipped and named in ``"unsupported_storage"``. Used by
    ``repro.launch.dryrun`` for decode cells and exercised directly by
    the disagg mesh tests.
    """
    from repro.core import autotune
    from repro.launch import analysis

    transfers = tuple(transfers)
    storages = tuple(storages)
    blocks = tuple(blocks)
    pre_rules = shd.PRESETS["serve_sp"]
    dec_rules = shd.PRESETS["serve_decode"]
    c_abs = transformer.abstract_cache(cfg, batch, seq_len)
    c_axes = transformer.cache_axes(cfg, batch, seq_len)
    pre_shard = shd.tree_shardings(c_abs, c_axes, mesh, pre_rules)
    dec_shard = shd.tree_shardings(c_abs, c_axes, mesh, dec_rules)
    p_abs = transformer.abstract_params(cfg)
    p_shard = shd.tree_shardings(p_abs, transformer.param_axes(cfg),
                                 mesh, dec_rules)
    slice_abs = transformer.abstract_cache(cfg, 1, seq_len)
    slice_axes = transformer.cache_axes(cfg, 1, seq_len)
    slice_pre = shd.tree_shardings(slice_abs, slice_axes, mesh, pre_rules)
    slot_sh = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())

    # whole-batch transfer + per-slot admission wire, per (transfer, block)
    # — the bf16 arm ignores the block, so it compiles once. Every leg a
    # family refuses is recorded in rep["skipped"] (flag -> the uniform
    # capability refusal), never silently omitted: the dryrun surfaces the
    # list in its reports, so a family whose metrics are absent from a
    # BENCH_roofline artifact names itself there.
    skipped = {}
    slot_ok = supports_slot_streaming(cfg)
    if not slot_ok:
        try:
            _require_slot_streaming(cfg)
        except NotImplementedError as e:
            skipped["--stream slots"] = str(e)
    t_coll, slot_coll = {}, {}
    for t in transfers:
        for blk in (blocks if t == "int8" else blocks[:1]):
            fn = make_cache_transfer_step(cfg, batch, seq_len, t, block=blk)
            with shd.axis_rules(mesh, dec_rules):
                hlo = jax.jit(fn, in_shardings=(pre_shard,),
                              out_shardings=dec_shard
                              ).lower(c_abs).compile().as_text()
            t_coll[(t, blk)] = analysis.hlo_collective_bytes(hlo)
            if not slot_ok:
                continue
            admit = make_slot_admit_step(cfg, batch, seq_len, t, "bf16",
                                         block=blk)
            with shd.axis_rules(mesh, dec_rules):
                hlo = jax.jit(
                    admit, in_shardings=(dec_shard, slice_pre, slot_sh),
                    out_shardings=dec_shard
                ).lower(c_abs, slice_abs,
                        jax.ShapeDtypeStruct((), jnp.int32)
                        ).compile().as_text()
            slot_coll[(t, blk)] = analysis.hlo_collective_bytes(hlo)

    def device_bytes(abs_tree, axes_tree):
        tot = 0.0
        for leaf, la in zip(jax.tree.leaves(abs_tree),
                            jax.tree.structure(abs_tree
                                               ).flatten_up_to(axes_tree)):
            spec = shd.resolve_spec(leaf.shape, tuple(la), mesh, dec_rules)
            shards = shd.spec_shard_count(spec, mesh)
            tot += float(np.prod(leaf.shape)) * leaf.dtype.itemsize / shards
        return int(tot)

    decodes, cache_bytes, unsupported = {}, {}, []
    batch_abs = {"tokens": jax.ShapeDtypeStruct((batch, 1), jnp.int32),
                 "pos": jax.ShapeDtypeStruct((), jnp.int32)}
    for s in storages:
        try:
            fn = step_lib.make_decode_step(cfg, seq_len, "bf16", s)
        except NotImplementedError as e:
            unsupported.append(s)
            skipped[f"kv_storage={s!r}"] = str(e)
            continue
        cs_abs = transformer.abstract_cache(cfg, batch, seq_len,
                                            kv_storage=s)
        cs_axes = transformer.cache_axes(cfg, batch, seq_len, kv_storage=s)
        cs_shard = shd.tree_shardings(cs_abs, cs_axes, mesh, dec_rules)
        with shd.axis_rules(mesh, dec_rules):
            hlo = jax.jit(fn, in_shardings=(p_shard, cs_shard, None),
                          out_shardings=(None, cs_shard)
                          ).lower(p_abs, cs_abs, batch_abs
                                  ).compile().as_text()
        decodes[s] = analysis.hlo_collective_bytes(hlo)
        cache_bytes[s] = device_bytes(cs_abs, cs_axes)

    # steady-state decode budget per admission: all batch slots serving
    # ~seq_len-token requests readmit one slot every seq_len/batch steps
    hide_steps = max(1, seq_len // max(1, batch))
    blk0 = blocks[0]

    def _tb(t, blk):
        return t_coll[(t, blk if t == "int8" else blk0)]

    def _sb(t, blk):
        return slot_coll[(t, blk if t == "int8" else blk0)]

    cells = {}
    for t in transfers:
        tcoll = _tb(t, blk0)
        for s, dcoll in decodes.items():
            tw = float(tcoll["total_wire_bytes_bf16eq"])
            dw = float(dcoll["total_wire_bytes_bf16eq"])
            cells[f"{t}x{s}"] = {
                "transfer_s": tw / ici_bw,
                "decode_step_s": dw / ici_bw,
                "collective_s": (tw + dw) / ici_bw,
                "transfer_wire_bytes_bf16eq": int(tw),
                "transfer_wire_bytes_bf16eq_s8":
                    int(tcoll["total_wire_bytes_bf16eq_s8"]),
                "decode_wire_bytes_bf16eq": int(dw),
                "cache_resident_bytes_per_device": cache_bytes[s],
            }
            if slot_ok:
                sw = float(_sb(t, blk0)["total_wire_bytes_bf16eq"])
                slot_s = sw / ici_bw
                hidden = min(slot_s, hide_steps * dw / ici_bw)
                cells[f"{t}x{s}"]["slot_stream_overlap_frac"] = \
                    1.0 if sw == 0 else hidden / slot_s

    slot_stream = {}
    for t in (transfers if slot_ok else ()):
        sc = _sb(t, blk0)
        slot_stream[t] = {
            "wire_bytes_bf16eq": int(sc["total_wire_bytes_bf16eq"]),
            "wire_bytes_bf16eq_s8":
                int(sc["total_wire_bytes_bf16eq_s8"]),
            "transfer_s": float(sc["total_wire_bytes_bf16eq"]) / ici_bw,
            "hide_steps": hide_steps,
        }

    block_sweep = {
        t: {int(blk): {
            "transfer_wire_bytes_bf16eq":
                int(_tb(t, blk)["total_wire_bytes_bf16eq"]),
            **({"slot_wire_bytes_bf16eq":
                int(_sb(t, blk)["total_wire_bytes_bf16eq"])}
               if slot_ok else {}),
        } for blk in (blocks if t == "int8" else blocks[:1])}
        for t in transfers}

    def objective(point):
        # wire (one transfer + one decode step) + the decode step's HBM
        # read of the resident cache — the term the storage arm halves
        tw = float(_tb(point["cache_transfer"],
                       point["block"])["total_wire_bytes_bf16eq"])
        s = point["kv_storage"]
        dw = float(decodes[s]["total_wire_bytes_bf16eq"])
        return (tw + dw) / ici_bw + cache_bytes[s] / hbm_bw

    tuned = None
    if decodes:
        res = autotune.tune_design(objective, {
            "cache_transfer": transfers,
            "kv_storage": tuple(decodes),
            "block": blocks,
        })
        tuned = {"point": res.best_point,
                 "collective_s": res.best_objective,
                 "evaluations": res.evaluations}

    return {"cells": cells, "unsupported_storage": unsupported,
            "skipped": skipped,
            "slot_stream": slot_stream, "block_sweep": block_sweep,
            "hide_steps": hide_steps, "tuned": tuned}


def fanin_report(cfg, batch: int, seq_len: int, *, workers: int = 2,
                 slots: int = 0, classes: int = 2, evict: str = "priority",
                 max_new: int = 0, decode_step_s: float = 0.0,
                 transfer_s: float = 0.0, page: int = 0,
                 kv_storage: str = "bf16"):
    """Deterministic fan-in roofline: drive the REAL
    :class:`repro.dist.fanin.AdmissionArbiter` through a contended
    serving trace and price the outcome with the disagg report's
    per-step costs. No wall clock, no jax — the same inputs always
    produce the same report (the determinism ``tests/test_serve_fanin.py``
    pins), so the keys gate in ``scripts/bench_diff.py``.

    ``batch`` requests with a seeded mixed-length spread and round-robin
    priority classes contend for a ``slots``-row table (default
    ``batch // 2`` — contention by construction) fed by ``workers``
    prefill workers; each simulated cycle is one decode step of cost
    ``decode_step_s``, and a dispatched prefill+transfer costs
    ``transfer_s``, double-buffered behind the queue wait. Reported
    (all flattened into decode dryrun cells' roofline):

    * ``fanin_admission_wait_s`` — mean per-admission latency: queue
      wait (arbiter passes lost x decode step) plus the transfer time
      the overlap failed to hide;
    * ``fanin_evictions`` — preemptions the policy performed (each costs
      a re-prefill of the extended prompt);
    * ``paged_hbm_bytes_per_slot`` vs ``slot_hbm_bytes_per_slot`` — the
      paged table's live-page resident rent per slot against the dense
      pad-to-horizon baseline (only for families with the ``paged``
      capability; refusals land in ``skipped`` like every other gated
      leg).
    """
    max_new = int(max_new) or max(1, seq_len // 8)
    n_slots = int(slots) or max(1, batch // 2)
    rng = np.random.RandomState(0)
    lens = rng.randint(max(1, seq_len // 4), seq_len + 1,
                       size=(batch,)).astype(np.int64)

    arb = fanin.AdmissionArbiter(workers=workers, classes=classes)
    reqs = [fanin.Request(rid=i, prompt=np.zeros((int(lens[i]),), np.int32),
                          max_new=max_new, priority=int(i % classes))
            for i in range(batch)]
    for r in reqs:
        arb.submit(r)
    remaining = {r.rid: max_new for r in reqs}
    emitted = {r.rid: 0 for r in reqs}
    occ: list = [None] * n_slots
    occ_req: list = [None] * n_slots
    wait_s: list = []
    cycles = 0
    limit = 1000 + 20 * batch * (max_new + n_slots + arb.promotion_cycles)

    def free_row(s):
        occ[s] = None
        occ_req[s] = None

    while True:
        arb.assign()
        while True:
            req = arb.next_admission()
            if req is None:
                break
            s = next((i for i in range(n_slots) if occ[i] is None), None)
            if s is None:
                s = arb.pick_victim(occ, evict, req)
                if s is None:
                    break
                victim = occ_req[s]
                arb.evicted(victim)
                victim.prompt = np.zeros(
                    (int(lens[victim.rid]) + emitted[victim.rid],),
                    np.int32)
                victim.max_new = remaining[victim.rid]
                free_row(s)
                arb.submit(victim, requeue=True)
            queue_wait = req.skips * decode_step_s
            wait_s.append(queue_wait + max(0.0, transfer_s - queue_wait))
            o = arb.admit(req)
            occ[s] = o
            occ_req[s] = req
            emitted[req.rid] += 1       # the prefill token
            remaining[req.rid] -= 1
            if remaining[req.rid] <= 0:
                free_row(s)
        arb.age()
        if all(o_ is None for o_ in occ):
            if not arb.queue:
                break
            continue
        cycles += 1                     # one decode step over the table
        for s in range(n_slots):
            r = occ_req[s]
            if r is None:
                continue
            emitted[r.rid] += 1
            remaining[r.rid] -= 1
            if remaining[r.rid] <= 0:
                free_row(s)
        if cycles > limit:
            raise RuntimeError("fan-in report simulation made no progress")

    rep = {"workers": workers, "slots": n_slots, "classes": classes,
           "evict": evict, "decode_cycles": cycles,
           "fanin_admission_wait_s":
               float(np.mean(wait_s)) if wait_s else 0.0,
           "fanin_evictions": int(arb.stats["evictions"]),
           "max_wait_passes": int(arb.stats["max_wait"]),
           "skipped": {}}

    caps = registry.capabilities(cfg)
    if caps.paged:
        base = seq_len + max_new
        P = int(page) or _default_page(base)
        total = -(-base // P) * P
        store = registry.paged_state_store(cfg, n_slots, total,
                                           kv_storage=kv_storage, page=P)
        per_pos = store.page_bytes() / P
        live = np.minimum(lens + max_new, total)
        paged_bytes = float(np.mean(-(-live // P) * P * per_pos))
        dense = sum(int(np.prod(l.shape)) * l.dtype.itemsize
                    for l in store.dense_abstract_state().values())
        rep["page"] = P
        rep["paged_hbm_bytes_per_slot"] = paged_bytes
        rep["slot_hbm_bytes_per_slot"] = float(dense / n_slots)
    else:
        try:
            registry.require(cfg, "paged", "--paged")
        except NotImplementedError as e:
            rep["skipped"]["--paged"] = str(e)
    return rep


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-8b")
    ap.add_argument("--full", action="store_true",
                    help="serve the published config instead of the "
                         "reduced smoke config (the default)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--tp", type=int, default=0,
                    help="model-parallel degree (0 = auto)")
    ap.add_argument("--preset", default="serve_sp",
                    choices=sorted(shd.PRESETS))
    ap.add_argument("--act-transport", default="bf16",
                    choices=list(step_lib.ACT_TRANSPORTS))
    ap.add_argument("--ragged", action="store_true",
                    help="serve a mixed-length batch (continuous batching)")
    ap.add_argument("--disagg", action="store_true",
                    help="disaggregate: prefill and decode on separate "
                         "meshes (half the devices each), the cache handed "
                         "off between them")
    ap.add_argument("--cache-transfer", default="bf16",
                    choices=list(step_lib.CACHE_TRANSFERS),
                    help="wire format of the disagg prefill->decode cache "
                         "handoff")
    ap.add_argument("--kv-storage", default="bf16",
                    choices=list(step_lib.KV_STORAGES),
                    help="decode-resident cache dtype (int8: s8 + scales, "
                         "f8: scale-free e4m3 — both ~halve cache HBM; "
                         "attention dequantizes/upcasts per block at read "
                         "time)")
    ap.add_argument("--stream", default="batch", choices=list(STREAMS),
                    help="handoff granularity: 'batch' prefills the whole "
                         "batch then decodes it; 'slots' streams each "
                         "request's cache slice into a running decode "
                         "batch via slot admission, transfers "
                         "double-buffered behind decode steps")
    ap.add_argument("--slots", type=int, default=0,
                    help="slot-table size of the slot engine (0 = one "
                         "slot per request; smaller forces slot reuse)")
    ap.add_argument("--workers", type=int, default=1,
                    help="prefill fan-in: N independent prefill workers "
                         "feeding one decode slot table through the "
                         "admission arbiter (>1, or --paged, selects the "
                         "slot engine's fan-in configuration; greedy only)")
    ap.add_argument("--evict", default="oldest",
                    choices=list(fanin.EVICTION_POLICIES),
                    help="slot preemption policy when the table is full "
                         "and a pending request outranks an occupant (or "
                         "hit the starvation promotion bound): the victim "
                         "requeues with its emitted tokens and is "
                         "re-prefilled on readmission (recompute "
                         "preemption)")
    ap.add_argument("--paged", action="store_true",
                    help="paged slot cache: slot rows are lists of "
                         "fixed-size pages in a shared pool with a "
                         "per-slot page table; admission ships only live "
                         "pages, pages allocate on demand, and requests "
                         "the unpaged horizon would refuse admit")
    ap.add_argument("--page-size", type=int, default=0,
                    help="positions per page for --paged (0 = the tuned "
                         "paged_attn registry point, default 256)")
    ap.add_argument("--pool-pages", type=int, default=0,
                    help="page-pool size backing the paged table (0 = "
                         "fully backed: slots x pages-per-row); "
                         "exhausting it is a loud error, never a stall")
    ap.add_argument("--horizon", type=int, default=0,
                    help="decode horizon in positions (0 = prompt-len + "
                         "max-new); an unpaged request that cannot fit "
                         "is refused loudly, never silently truncated — "
                         "--paged admits it instead")
    ap.add_argument("--priority-classes", type=int, default=1,
                    help="admission priority classes for the fan-in "
                         "arbiter, round-robin assigned to the smoke "
                         "batch (0 = most urgent; with >1, --evict "
                         "priority preempts lower classes)")
    return ap


def resolve_config(args):
    """--full lowers the published config; the default is the smoke
    config (same family and code paths, CPU-runnable dims)."""
    return get_config(args.arch) if args.full else smoke_config(args.arch)


def setup(args):
    """Everything ``main`` serves with, from its parsed arguments: the
    config, the parameters (built under jit straight into the prefill
    mesh's placement), the seeded prompt batch and ``generate``'s keyword
    arguments: ``main`` is ``generate(cfg, params, prompts, **kwargs)``
    plus reporting.
    """
    cfg = resolve_config(args)
    if not cfg.supports_decode:
        raise SystemExit(f"{cfg.name} is encoder-only; no decode serving")

    fan_in = args.workers > 1 or args.paged
    prefill_meshes = None
    decode_mesh = decode_rules = None
    if args.disagg:
        if fan_in:
            prefill_meshes, decode_mesh = make_fanin_meshes(
                cfg, max(1, args.workers), args.tp, args.tp)
            mesh = prefill_meshes[0]
        else:
            mesh, decode_mesh = make_disagg_meshes(cfg, args.tp, args.tp)
        rules = shd.PRESETS[args.preset]
        decode_rules = shd.PRESETS["serve_decode"]
    else:
        tp = args.tp or _pick_tp(jax.device_count(), cfg)
        mesh = make_local_mesh(model_parallel=tp)
        rules = shd.PRESETS[args.preset]

    params = transformer.init_params(
        cfg, jax.random.PRNGKey(0),
        shd.tree_shardings(transformer.abstract_params(cfg),
                           transformer.param_axes(cfg), mesh, rules))
    rng = np.random.RandomState(0)
    prompts = rng.randint(0, cfg.vocab,
                          size=(args.batch, args.prompt_len)).astype(np.int32)
    lens = None
    if args.ragged:
        lens = rng.randint(max(1, args.prompt_len // 2), args.prompt_len + 1,
                           size=(args.batch,)).astype(np.int32)

    prios = None
    if args.priority_classes > 1:
        prios = (np.arange(args.batch)
                 % args.priority_classes).astype(np.int32)
    kwargs = dict(max_new=args.max_new, temperature=args.temperature,
                  prompt_lens=lens, mesh=mesh, rules=rules,
                  act_transport=args.act_transport, decode_mesh=decode_mesh,
                  decode_rules=decode_rules,
                  cache_transfer=args.cache_transfer,
                  kv_storage=args.kv_storage, stream=args.stream,
                  slots=args.slots, workers=args.workers, evict=args.evict,
                  paged=args.paged, page_size=args.page_size,
                  pool_pages=args.pool_pages, horizon=args.horizon,
                  priorities=prios, prefill_meshes=prefill_meshes)
    return cfg, params, prompts, kwargs


def main(argv=None) -> None:
    args = build_parser().parse_args(argv)
    enable_compile_cache(disaggregated=args.disagg)
    cfg, params, prompts, kwargs = setup(args)
    mesh, decode_mesh = kwargs["mesh"], kwargs["decode_mesh"]
    lens = kwargs["prompt_lens"]
    fan_in = args.workers > 1 or args.paged

    t0 = time.time()
    out = generate(cfg, params, prompts, **kwargs)
    dt = time.time() - t0
    n_tok = out.size
    mesh_desc = dict(zip(mesh.axis_names, mesh.devices.shape))
    if decode_mesh is not None:
        mesh_desc = {"prefill": dict(zip(mesh.axis_names,
                                         mesh.devices.shape)),
                     "decode": dict(zip(decode_mesh.axis_names,
                                        decode_mesh.devices.shape))}
    print(f"[serve] arch={cfg.name} batch={args.batch} "
          f"prompt={args.prompt_len} new={args.max_new} "
          f"mesh={mesh_desc} "
          f"preset={args.preset} act_transport={args.act_transport} "
          f"disagg={args.disagg} cache_transfer={args.cache_transfer} "
          f"kv_storage={args.kv_storage} stream={args.stream}"
          + (f" lens={lens.tolist()}" if lens is not None else ""))
    print(f"[serve] generated {n_tok} tokens in {dt:.2f}s "
          f"({n_tok/dt:.1f} tok/s incl. compile)")
    if fan_in or args.stream == "slots":
        st = _generate_slots.last_stats
        if fan_in:
            print(f"[serve] fan-in: workers={args.workers} "
                  f"evict={args.evict} admissions={st['admissions']} "
                  f"evictions={st['evictions']} requeues={st['requeues']} "
                  f"decode_steps={st['decode_steps']} "
                  f"transfer_wait_s={st['transfer_wait_s']:.3f} "
                  f"max_wait_passes={st['max_wait_passes']}")
        else:
            print(f"[serve] slot stream: admissions={st['admissions']} "
                  f"decode_steps={st['decode_steps']} "
                  f"transfer_wait_s={st['transfer_wait_s']:.3f} "
                  "(wire time the double buffer failed to hide behind "
                  "decode)")
        if args.paged:
            print(f"[serve] paged: page={st['page']} "
                  f"peak_live_pages={st['peak_live_pages']} "
                  f"hbm_bytes_per_slot={st['hbm_bytes_per_slot']} "
                  f"(dense pad-to-horizon "
                  f"{st['dense_hbm_bytes_per_slot']})")
    print("[serve] sample:", out[0][:10])


if __name__ == "__main__":
    main()
