"""Seeded random weights of the MLA + held-share expert decoder
(DeepSeek-V3 block), made as ``weights.py`` makes them: leaf by leaf
from the seed, every layer of a stacked leaf from its own key, so the
reference can make any one layer alone and get the same numbers.

This adds to ``weights.py``'s rules the leaves that model has and the
MiniCPM3 block has not: the direct query projection ``wq``, the router,
its selection bias (normal with the configuration's ``router_bias_std``)
and the experts' stacked SwiGLU weights ``(E, d, f)``/``(E, f, d)``,
each normal with variance one over the width it contracts. Stacked
leaves are those under ``layers/`` (norms and attention, every layer),
``dense_mlp/`` (the leading dense layers) and ``moe/`` (the expert
layers).
"""

from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from bench.harness import weights as wlib

STACKS = ("layers", "dense_mlp", "moe")
EXPERT_LEAVES = ("w_gate", "w_up", "w_down")     # (E, in, out)
DIRECT = ("wq", "router")                         # (in, out...)


def draw(key, path: str, shape: Tuple[int, ...], bias_std: float,
         dtype=jnp.bfloat16):
    """One leaf (one layer of it, for stacked leaves)."""
    name = path.rsplit("/", 1)[-1]
    if name == "router_bias":
        std = bias_std
    elif name in EXPERT_LEAVES:
        std = 1.0 / math.sqrt(shape[1])
    elif name in DIRECT:
        std = 1.0 / math.sqrt(shape[0])
    else:
        return wlib.draw(key, path, shape, dtype)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def layer_leaf(base, path: str, shape: Tuple[int, ...], layer,
               bias_std: float, dtype=jnp.bfloat16):
    return draw(jax.random.fold_in(wlib.leaf_key(base, path), layer), path,
                shape, bias_std, dtype)


def build(seed: int, abstract, bias_std: float, shardings=None,
          dtype=jnp.bfloat16):
    """The tree of ``abstract`` made in one jitted call, in ``dtype``,
    placed by ``shardings`` when given."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    paths = [wlib.path_str(kp) for kp, _ in leaves]
    shapes = [tuple(a.shape) for _, a in leaves]

    def make(base):
        out = []
        for path, shape in zip(paths, shapes):
            if path.split("/", 1)[0] in STACKS:
                out.append(jax.vmap(
                    lambda l, p=path, s=shape[1:]: layer_leaf(
                        base, p, s, l, bias_std, dtype))(
                            jnp.arange(shape[0])))
            else:
                out.append(draw(wlib.leaf_key(base, path), path, shape,
                                bias_std, dtype))
        return jax.tree_util.tree_unflatten(treedef, out)
    placed = {} if shardings is None else {"out_shardings": shardings}
    return jax.jit(make, **placed)(wlib.base_key(seed))
