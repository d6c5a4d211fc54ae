"""Seeded random weights for the benchmark's dense GQA configurations.

One function, :func:`make_leaf`, draws every weight from the seed by its
name and layer. The served model gets all of them from one jitted call,
in the type they are served in (:func:`make_all`); the plain reference
draws one layer at a time (:func:`make_layer`) and gets the same numbers.
Nothing here reads the program: the layout is the published one
(Qwen2-style blocks with QKV bias), named by the paths under which the
program's parameter tree keeps them.
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
class Dims:
    """The sizes one forward pass needs, read from a configuration file."""
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    rms_eps: float
    tied: bool

    @classmethod
    def from_config(cls, cfg: dict) -> "Dims":
        heads = int(cfg["num_attention_heads"])
        d = int(cfg["hidden_size"])
        return cls(layers=int(cfg["num_hidden_layers"]), d_model=d,
                   heads=heads, kv_heads=int(cfg["num_key_value_heads"]),
                   head_dim=int(cfg.get("head_dim", d // heads)),
                   d_ff=int(cfg["intermediate_size"]),
                   vocab=int(cfg["vocab_size"]),
                   rope_theta=float(cfg["rope_theta"]),
                   rms_eps=float(cfg["rms_norm_eps"]),
                   tied=bool(cfg["tie_word_embeddings"]))


@dataclasses.dataclass(frozen=True)
class Leaf:
    name: str             # program path, "/"-joined
    shape: Tuple[int, ...]  # one layer's shape for per-layer leaves
    per_layer: bool
    kind: str             # "norm" | "bias" | "embed" | "dense"
    fan_in: int = 1


def layout(dims: Dims) -> List[Leaf]:
    d, h, kv, hd, f = (dims.d_model, dims.heads, dims.kv_heads,
                       dims.head_dim, dims.d_ff)
    leaves = [
        Leaf("embed/embedding", (dims.vocab, d), False, "embed", d),
        Leaf("final_norm/scale", (d,), False, "norm"),
        Leaf("layers/attn_norm/scale", (d,), True, "norm"),
        Leaf("layers/attn/q/w", (d, h, hd), True, "dense", d),
        Leaf("layers/attn/q/b", (h, hd), True, "bias"),
        Leaf("layers/attn/k/w", (d, kv, hd), True, "dense", d),
        Leaf("layers/attn/k/b", (kv, hd), True, "bias"),
        Leaf("layers/attn/v/w", (d, kv, hd), True, "dense", d),
        Leaf("layers/attn/v/b", (kv, hd), True, "bias"),
        Leaf("layers/attn/o/w", (h, hd, d), True, "dense", h * hd),
        Leaf("layers/ffn_norm/scale", (d,), True, "norm"),
        Leaf("layers/ffn/gate/w", (d, f), True, "dense", d),
        Leaf("layers/ffn/up/w", (d, f), True, "dense", d),
        Leaf("layers/ffn/down/w", (f, d), True, "dense", f),
    ]
    if not dims.tied:
        leaves.append(Leaf("unembed/w", (d, dims.vocab), False, "dense", d))
    return leaves


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


def make_all(dims: Dims, seed: int, dtype) -> Dict[str, jax.Array]:
    """Every leaf, per-layer leaves stacked on a leading layer axis, made
    on the device by one jitted program. The per-layer draws are vmapped
    over the layers' keys, which gives the numbers :func:`make_leaf`
    gives one layer at a time."""
    leaves = layout(dims)

    def build(words):
        out = {}
        for leaf in leaves:
            if leaf.per_layer:
                keys = jnp.stack([_leaf_key(words, leaf.name, i)
                                  for i in range(dims.layers)])
            else:
                keys = _leaf_key(words, leaf.name, None)[None]
            z = jax.vmap(lambda k, s=leaf.shape: jax.random.normal(
                k, s, jnp.float32))(keys)
            out[leaf.name] = _scaled(leaf, z if leaf.per_layer else z[0],
                                     dtype)
        return out

    return jax.jit(build)(seed_words(seed))


def make_layer(dims: Dims, seed: int, layer: int, dtype=jnp.float32):
    """One layer's leaves, as :func:`make_all` holds them, by short name
    (``attn/q/w`` ...), cast to ``dtype`` from the served type."""
    served = DTYPES["bfloat16"]
    return {leaf.name[len("layers/"):]:
            make_leaf(seed, leaf, layer, served).astype(dtype)
            for leaf in layout(dims) if leaf.per_layer}


def make_global(dims: Dims, seed: int, name: str, dtype=jnp.float32):
    leaf = next(x for x in layout(dims) if x.name == name)
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
