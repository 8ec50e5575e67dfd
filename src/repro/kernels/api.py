"""Tunable-op registry: one surface for every Pallas kernel family.

Each kernel subpackage used to carry its own copy of the same plumbing —
a ``_use_interpret()`` backend probe, a ``use_ref=`` escape hatch, and
hard-coded block-size defaults. This module replaces those four divergent
entry points with one registry: an op declares

  * its tunable axes (name -> ordered candidate values) and the
    deterministic default point (the pre-registry hard-coded blocks),
  * its kernel path (``run(point, *args, **kw)``) and pure-jnp ref impl,
  * a ``clamp`` rule that fits any tuned/passed point to the actual
    operand extents (a point cached from a long shape must not fail or
    mis-grid on a shorter one),
  * a ``shape_key`` that names the (shape, dtype) cell a tuned point is
    cached under, and
  * representative ``example`` shapes the sweep harness tunes on.

``call(name, ...)`` is the single dispatch: resolve the point (explicit
override > persisted tuned cache (repro.kernels.tuned) > default), clamp
it, run. ``core.autotune.tune_design`` sweeps any registered op
generically through ``repro.kernels.tune``; new kernels (paged-slot
cache, expert all-to-all) register here instead of re-plumbing.

``exact_axes`` names the axes along which the op's output is provably
invariant bit-for-bit (pure data movement, or tiling that never regroups
a reduction): the property suite pins those, and tolerates only fp
reassociation on the rest (e.g. flash's ``block_k`` splits the online
softmax differently).
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib
import math
from typing import Any, Callable, Dict, Mapping, Optional, Sequence, Tuple

import jax


def use_interpret() -> bool:
    """Interpret-mode rule shared by every registered op: Mosaic kernels
    compile on TPU and are interpreted on the CPU backend (tests, local
    runs). Any other backend is an error, never a silent interpreter."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas TPU kernels cannot run on the {backend!r} backend: they "
        f"compile for TPU and are interpreted only on CPU")


def fit_block(value: int, extent: int) -> int:
    """Clamp a block size to an operand extent, keeping divisibility.

    Every kernel grid requires ``extent % block == 0``. A tuned point
    cached from a long shape (say block 512 from seq 4096) applied to a
    shorter one must degrade deterministically, never assert: clamp to
    the extent, and if the clamped value does not divide it, fall back to
    gcd(value, extent) — always a divisor, always <= value.
    """
    if extent <= 0:
        return max(1, value)
    v = min(int(value), extent)
    if v <= 0:
        v = 1
    if extent % v == 0:
        return v
    return math.gcd(v, extent)


@dataclasses.dataclass(frozen=True)
class TunableOp:
    """One registered kernel family and everything the sweep needs."""
    name: str
    axes: Mapping[str, Tuple]            # axis -> ordered candidate values
    default: Mapping[str, Any]           # the pre-registry hard-coded point
    run: Callable                        # run(point, *args, **kw) -> out
    ref: Callable                        # ref(*args, **kw) -> out
    clamp: Callable                      # clamp(point, *args, **kw) -> point
    shape_key: Callable                  # shape_key(*args, **kw) -> str
    example: Callable                    # example(quick: bool) -> (args, kw)
    exact_axes: frozenset = frozenset()  # axes that provably keep bits
    tol: float = 0.0                     # |kernel - ref| bound (0 = exact)


_REGISTRY: Dict[str, TunableOp] = {}

# ops.py modules that register the built-in kernel families on import;
# imported lazily so `repro.kernels.api` never cycles with the packages
# that import it.
_BUILTIN_OPS = (
    "repro.kernels.compact_pack.ops",
    "repro.kernels.flash_attn.ops",
    "repro.kernels.decode_attn.ops",
    "repro.kernels.paged_attn.ops",
    "repro.kernels.rmsnorm.ops",
    "repro.kernels.expert_a2a.ops",
    "repro.kernels.expert_gmm.ops",
)


def register(op: TunableOp) -> TunableOp:
    for axis in op.default:
        if axis not in op.axes:
            raise ValueError(f"{op.name}: default names unknown axis {axis!r}")
    for axis, vals in op.axes.items():
        if axis not in op.default:
            raise ValueError(f"{op.name}: axis {axis!r} has no default")
        if op.default[axis] not in vals:
            raise ValueError(f"{op.name}: default {op.default[axis]!r} not "
                             f"among candidates for axis {axis!r}")
    _REGISTRY[op.name] = op
    return op


def ensure_registered() -> None:
    for mod in _BUILTIN_OPS:
        importlib.import_module(mod)


def get_op(name: str) -> TunableOp:
    if name not in _REGISTRY:
        ensure_registered()
    return _REGISTRY[name]


def ops() -> Dict[str, TunableOp]:
    ensure_registered()
    return dict(_REGISTRY)


def default_point(op: TunableOp) -> Dict[str, Any]:
    return dict(op.default)


def _resolve(op: TunableOp, shape_key: str) -> Tuple[Dict[str, Any], str]:
    from repro.kernels import tuned  # local: keep api import-light

    point = default_point(op)
    cached = tuned.lookup(op.name, shape_key)
    if not cached:
        return point, "default"
    for axis in op.axes:
        if axis in cached:
            point[axis] = cached[axis]
    return point, "tuned"


def resolve_point(op: TunableOp, *args, **kwargs) -> Dict[str, Any]:
    """Tuned-cache lookup at op-call time, deterministic default fallback.

    Cache entries are keyed (op, shape_key, device_kind); a miss — no
    file, unknown shape, stale device kind, corrupt JSON — silently
    yields the default point, so serving never depends on a sweep having
    run. Unknown axes in a cached point (an older/newer schema) are
    dropped rather than trusted.
    """
    return _resolve(op, op.shape_key(*args, **kwargs))[0]


@dataclasses.dataclass(frozen=True)
class Dispatch:
    """One kernel dispatch: the clamped point it ran and where that point
    came from ("explicit", "tuned" from the persisted cache, "default")."""
    op: str
    shape_key: str
    point: Dict[str, Any]
    source: str


_recorders: list = []            # open record_dispatches() logs


@contextlib.contextmanager
def record_dispatches():
    """Collect every kernel dispatch made inside the block, in order, as
    :class:`Dispatch` records (``use_ref`` calls run no kernel and are
    not recorded)."""
    log: list = []
    _recorders.append(log)
    try:
        yield log
    finally:
        _recorders.remove(log)


def call(name: str, *args, point: Optional[Mapping[str, Any]] = None,
         use_ref: bool = False, **kwargs):
    """Dispatch one op: explicit point > tuned cache > default, clamped."""
    op = get_op(name)
    if use_ref:
        return op.ref(*args, **kwargs)
    key = op.shape_key(*args, **kwargs)
    if point is None:
        point, source = _resolve(op, key)
    else:
        merged = default_point(op)
        merged.update({a: v for a, v in point.items() if a in op.axes})
        point, source = merged, "explicit"
    point = op.clamp(dict(point), *args, **kwargs)
    for log in _recorders:
        log.append(Dispatch(name, key, dict(point), source))
    return op.run(point, *args, **kwargs)


def clamped_axes(op: TunableOp, *args, **kwargs) -> Dict[str, Tuple]:
    """The op's candidate values after clamping to these operands, deduped
    in candidate order — the space ``tune_design`` actually sweeps (a
    short shape collapses oversized candidates onto the extent instead of
    wasting evaluations on aliases)."""
    out: Dict[str, Tuple] = {}
    base = default_point(op)
    for axis, vals in op.axes.items():
        seen = []
        for v in vals:
            c = op.clamp({**base, axis: v}, *args, **kwargs)[axis]
            if c not in seen:
                seen.append(c)
        out[axis] = tuple(seen)
    return out
