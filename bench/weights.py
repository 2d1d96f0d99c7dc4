"""Model weights from the run's seed, in the serving program's layout.

The benchmark makes the weights, so that the plain reference can make the
same values on its own, once the program's state is freed: leaf ``i`` of
layer ``l`` is drawn from ``fold_in(fold_in(model_key, i), l)``.  ``init``
makes every leaf of a model in one jitted call, layers stacked on a leading
axis as the program keeps them.

Norm scales are stored as offsets from 1 (the program computes
``x * (1 + g)``).  Matrices are N(0, 1/fan_in); norm offsets and biases are
N(0, 0.1), so that a lost bias or norm shows in the logits; the embedding
is N(0, 1).
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp

from spec import ModelSpec

NORM_STD = 0.1
BIAS_STD = 0.1


@dataclasses.dataclass(frozen=True)
class Leaf:
    path: tuple[str, ...]
    shape: tuple[int, ...]       # of one layer, for a per-layer leaf
    std: float
    dtype: str
    per_layer: bool


def leaves(m: ModelSpec) -> tuple[Leaf, ...]:
    """Every parameter of ``m``, in a fixed order that numbers the keys."""
    d, h, kv, hd, f = m.d_model, m.heads, m.kv_heads, m.head_dim, m.d_ff
    w = m.dtype
    out = [Leaf(("embed",), (m.vocab, d), 1.0, w, False),
           Leaf(("ln_f",), (d,), NORM_STD, "float32", False)]
    if not m.tied:
        out.append(Leaf(("head",), (d, m.vocab), d ** -0.5, w, False))
    blk = ("blocks",)
    out += [
        Leaf(blk + ("ln_attn",), (d,), NORM_STD, "float32", True),
        Leaf(blk + ("ln_ffn",), (d,), NORM_STD, "float32", True),
        Leaf(blk + ("attn", "wq"), (d, h, hd), d ** -0.5, w, True),
        Leaf(blk + ("attn", "wk"), (d, kv, hd), d ** -0.5, w, True),
        Leaf(blk + ("attn", "wv"), (d, kv, hd), d ** -0.5, w, True),
        Leaf(blk + ("attn", "wo"), (h, hd, d), (h * hd) ** -0.5, w, True),
        Leaf(blk + ("ffn", "gate"), (d, f), d ** -0.5, w, True),
        Leaf(blk + ("ffn", "up"), (d, f), d ** -0.5, w, True),
        Leaf(blk + ("ffn", "down"), (f, d), f ** -0.5, w, True),
    ]
    if m.qkv_bias:
        out += [Leaf(blk + ("attn", "bq"), (h, hd), BIAS_STD, w, True),
                Leaf(blk + ("attn", "bk"), (kv, hd), BIAS_STD, w, True),
                Leaf(blk + ("attn", "bv"), (kv, hd), BIAS_STD, w, True)]
    if m.qk_norm:
        out += [Leaf(blk + ("attn", "q_norm"), (hd,), NORM_STD, "float32",
                     True),
                Leaf(blk + ("attn", "k_norm"), (hd,), NORM_STD, "float32",
                     True)]
    return tuple(out)


def model_key(seed: int, m: ModelSpec) -> jax.Array:
    """The model's key from a seed of up to 64 bits."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(key, m.seed_offset)


def _draw(key, leaf: Leaf):
    x = jax.random.normal(key, leaf.shape, jnp.float32) * leaf.std
    return x.astype(jnp.dtype(leaf.dtype))


def _put(tree: dict, path: tuple[str, ...], value) -> None:
    for p in path[:-1]:
        tree = tree.setdefault(p, {})
    tree[path[-1]] = value


def _init(m: ModelSpec, key):
    tree: dict = {}
    for i, leaf in enumerate(leaves(m)):
        k = jax.random.fold_in(key, i)
        if leaf.per_layer:
            keys = jax.vmap(partial(jax.random.fold_in, k))(
                jnp.arange(m.layers))
            value = jax.vmap(lambda kk: _draw(kk, leaf))(keys)
        else:
            value = _draw(k, leaf)
        _put(tree, leaf.path, value)
    return tree


_init_jit = jax.jit(_init, static_argnums=0)


def init(m: ModelSpec, seed: int, device) -> dict:
    """All of ``m``'s parameters on ``device``, in one jitted call (one
    compile serves every model of the same shape)."""
    key = jax.device_put(model_key(seed, m), device)
    return _init_jit(dataclasses.replace(m, alias="", seed_offset=0), key)


def param_count(m: ModelSpec) -> int:
    return sum(math.prod(leaf.shape) * (m.layers if leaf.per_layer else 1)
               for leaf in leaves(m))
