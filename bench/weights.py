"""Random weights from the seed, drawn on the device in one jitted call.

The program says only which leaves it has and their shapes and dtype
(its abstract parameter tree); the values are the benchmark's own.  Each
leaf's distribution follows from its name: norm scales near 1, token-
shift mixes in [0, 1], RWKV decay logits spanning slow to fast decay,
small nonzero biases (so the reference checks the bias path), and
fan-in scaled normals for every projection.

Embeddings: std 0.02.  A table that is also the output head (tied) is
drawn at 0.02 / sqrt(d) instead, so that the rows the layers see after
the program's sqrt(d) input scale have std 0.02, as Qwen2's do.  Drawn
at 0.02, each token's own row dominates the residual stream and the tied
head hands the same token back: greedy output is one token repeated, at
margins no rounding can flip, and the correctness comparison sees
nothing.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _key_name(path) -> str:
    for entry in reversed(path):
        name = getattr(entry, "key", None)
        if isinstance(name, str):
            return name
    raise ValueError(f"no dict key on path {path}")


def _draw(name: str, key, shape, dtype, tied: bool = False):
    # Drawn in the served dtype: no float32 copy of a large leaf.
    normal = lambda s: jax.random.normal(key, shape, dtype) * s  # noqa: E731
    if name == "scale":
        x = 1.0 + normal(0.1)
    elif name == "mu":
        x = jax.random.uniform(key, shape, jnp.float32)
    elif name == "w_decay_base":
        x = jax.random.uniform(key, shape, jnp.float32, -5.0, 0.5)
    elif name == "u_bonus":
        x = normal(0.5)
    elif name in ("bq", "bk", "bv"):
        x = normal(0.2)
    elif name == "embedding":
        x = normal(0.02 / shape[-1] ** 0.5 if tied else 0.02)
    elif len(shape) >= 2:
        x = normal(shape[-2] ** -0.5)
    else:
        raise ValueError(f"no distribution for leaf {name!r} {shape}")
    return x.astype(dtype)


def draw(abstract_tree, seed: int):
    """Concrete weights shaped like ``abstract_tree`` (ShapeDtypeStructs),
    on the default device, from ``seed``."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract_tree)
    tied = not any(_key_name(path) == "unembed" for path, _ in leaves)

    @jax.jit
    def make(key):
        out = [_draw(_key_name(path), jax.random.fold_in(key, i), a.shape,
                     a.dtype, tied)
               for i, (path, a) in enumerate(leaves)]
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.block_until_ready(make(jax.random.key(seed)))
