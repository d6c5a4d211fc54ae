"""Seeded random weights for the benchmark's configurations.

One function, :func:`make_leaf`, draws every weight from the seed by its
name and layer. The served model gets all of them from one jitted call,
in the type they are served in (:func:`make_all`); the plain reference
draws one layer at a time (:func:`make_layer`) and gets the same numbers.
Nothing here reads the program or knows an architecture: the leaves are
a layout that the configuration's architecture (``chipbench/archs/``)
gives, named by the paths under which the program's parameter tree
keeps them.
"""
from __future__ import annotations

import dataclasses
import zlib
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


@dataclasses.dataclass(frozen=True)
class Leaf:
    name: str             # program path, "/"-joined
    shape: Tuple[int, ...]  # one layer's shape for per-layer leaves
    per_layer: bool
    kind: str             # "norm" | "bias" | "embed" | "dense"
    fan_in: int = 1
    depth: Optional[int] = None  # layers a per-layer leaf stacks; None: all


def seed_words(seed: int) -> np.ndarray:
    """--seed may exceed 32 bits: its low and high 32-bit halves."""
    return np.array([seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF],
                    np.uint32)


def _leaf_key(words, name: str, layer: Optional[int]):
    """``words`` is :func:`seed_words` of the seed, concrete or traced:
    the seed is an input of the jitted init, never a constant in it, so
    one compiled init serves every seed."""
    key = jax.random.PRNGKey(words[0])
    key = jax.random.fold_in(key, words[1])
    key = jax.random.fold_in(key, zlib.crc32(name.encode()))
    if layer is not None:
        key = jax.random.fold_in(key, layer)
    return key


def _scaled(leaf: Leaf, z, dtype):
    if leaf.kind == "norm":
        x = 1.0 + 0.1 * z
    elif leaf.kind == "bias":
        x = 0.1 * z
    else:                      # embed and dense: unit-variance outputs
        x = z * (1.0 / np.sqrt(leaf.fan_in))
    return x.astype(dtype)


def make_leaf(seed: int, leaf: Leaf, layer: Optional[int], dtype):
    """One leaf (one layer's slice of a per-layer leaf) in ``dtype``."""
    z = jax.random.normal(_leaf_key(seed_words(seed), leaf.name, layer),
                          leaf.shape, jnp.float32)
    return _scaled(leaf, z, dtype)


def make_all(leaves: List[Leaf], layers: int, seed: int,
             dtype) -> Dict[str, jax.Array]:
    """Every leaf, per-layer leaves stacked on a leading layer axis of
    their ``depth`` (``layers`` where it is None), made on the device by
    one jitted program. The per-layer draws are vmapped over the layers'
    keys, which gives the numbers :func:`make_leaf` gives one layer at a
    time."""

    def build(words):
        out = {}
        for leaf in leaves:
            if leaf.per_layer:
                keys = jnp.stack([_leaf_key(words, leaf.name, i)
                                  for i in range(leaf.depth or layers)])
            else:
                keys = _leaf_key(words, leaf.name, None)[None]
            z = jax.vmap(lambda k, s=leaf.shape: jax.random.normal(
                k, s, jnp.float32))(keys)
            out[leaf.name] = _scaled(leaf, z if leaf.per_layer else z[0],
                                     dtype)
        return out

    return jax.jit(build)(seed_words(seed))


def make_layer(leaves: List[Leaf], seed: int, layer: int,
               dtype=jnp.float32):
    """Layer ``layer`` of the per-layer leaves that stack it, as
    :func:`make_all` holds them, by the name under their stack (``attn/q/w``
    for ``layers/attn/q/w`` ...), cast to ``dtype`` from the served
    type. Where two stacks hold a layer, pass one stack's leaves."""
    served = DTYPES["bfloat16"]
    out = {}
    for leaf in leaves:
        if leaf.per_layer and (leaf.depth is None or layer < leaf.depth):
            short = leaf.name.split("/", 1)[1]
            if short in out:
                raise ValueError(f"two stacks hold {short!r} at layer "
                                 f"{layer}")
            out[short] = make_leaf(seed, leaf, layer, served).astype(dtype)
    return out


def make_global(leaves: List[Leaf], seed: int, name: str,
                dtype=jnp.float32):
    leaf = next(x for x in leaves if x.name == name)
    return make_leaf(seed, leaf, None, DTYPES["bfloat16"]).astype(dtype)


def to_program_tree(shape_tree, flat: Dict[str, jax.Array]):
    """Fill the program's parameter tree (as ``jax.eval_shape`` of its
    init gives it) with ``flat``; every path and shape has to match."""
    seen = set()

    def fill(path, p):
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        if name not in flat:
            raise KeyError(f"program parameter {name!r} has no weight in "
                           f"the benchmark's layout")
        v = flat[name]
        if tuple(v.shape) != tuple(p.value.shape):
            raise ValueError(f"{name}: benchmark shape {tuple(v.shape)} != "
                             f"program shape {tuple(p.value.shape)}")
        seen.add(name)
        return dataclasses.replace(p, value=v)

    from repro.models import common as cm  # the system under test's type
    tree = jax.tree_util.tree_map_with_path(
        fill, shape_tree,
        is_leaf=cm.is_param)
    missing = set(flat) - seen
    if missing:
        raise KeyError(f"benchmark weights the program does not take: "
                       f"{sorted(missing)}")
    return tree
