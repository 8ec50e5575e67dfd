"""Seeded random weights of a decoder, made leaf by leaf from the seed.

Every leaf draws from its own key, ``fold_in(base(seed), crc32(path))``,
and every layer of a stacked leaf (path under ``layers/``) from
``fold_in(leaf_key, layer)``: so the whole tree is made on the device in
one jitted call, in bf16, and the reference can make any one layer alone
and get the same numbers. Norm scales are ones; the embedding is
standard normal; every other matrix is normal with variance one over the
number of inputs it contracts.
"""

from __future__ import annotations

import math
import zlib
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

STACK = "layers"
NORMS = frozenset({"ln1", "ln2", "q_norm", "kv_norm", "final_norm"})
# leading axes each matrix contracts with its input
CONTRACTED = {"embed": 0, "wq_a": 1, "wq_b": 1, "wkv_a": 1, "wk_b": 1,
              "wv_b": 1, "wo": 2, "gate": 1, "up": 1, "down": 1,
              "lm_head": 1}


def base_key(seed: int):
    """A key from any whole number: both 32-bit halves count."""
    k = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 32) & 0x7FFFFFFF)


def leaf_key(base, path: str):
    return jax.random.fold_in(base, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def draw(key, path: str, shape: Tuple[int, ...], dtype=jnp.bfloat16):
    """One leaf (one layer of it, for stacked leaves) of shape ``shape``."""
    name = path.rsplit("/", 1)[-1]
    if name in NORMS:
        return jnp.ones(shape, dtype)
    if name not in CONTRACTED:
        raise ValueError(f"no initialisation rule for weight {path!r}")
    fan_in = math.prod(shape[:CONTRACTED[name]])
    std = 1.0 / math.sqrt(fan_in) if CONTRACTED[name] else 1.0
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def layer_leaf(base, path: str, shape: Tuple[int, ...], layer):
    return draw(jax.random.fold_in(leaf_key(base, path), layer), path, shape)


def path_str(keypath) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in keypath)


def build(seed: int, abstract, shardings=None):
    """The tree of ``abstract`` (ShapeDtypeStructs; a leaf under
    ``layers/`` has the layer count as its first axis), made in one
    jitted call, placed by ``shardings`` when given."""
    placed = {} if shardings is None else {"out_shardings": shardings}
    return jax.jit(maker(abstract), **placed)(base_key(seed))


def maker(abstract):
    """``make(base_key) -> tree``, the function :func:`build` jits."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    paths = [path_str(kp) for kp, _ in leaves]
    shapes = [tuple(a.shape) for _, a in leaves]

    def make(base):
        out = []
        for path, shape in zip(paths, shapes):
            if path.startswith(STACK + "/"):
                out.append(jax.vmap(
                    lambda l, p=path, s=shape[1:]: layer_leaf(base, p, s, l)
                )(jnp.arange(shape[0])))
            else:
                out.append(draw(leaf_key(base, path), path, shape))
        return jax.tree_util.tree_unflatten(treedef, out)
    return make


def shapes_of(abstract) -> Dict[str, Tuple[int, ...]]:
    """``{path: shape}`` of a tree, as :func:`build` names its leaves."""
    leaves, _ = jax.tree_util.tree_flatten_with_path(abstract)
    return {path_str(kp): tuple(a.shape) for kp, a in leaves}
