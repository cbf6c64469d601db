"""Seeded weights, made on the device in one jitted call.

The benchmark draws every weight itself, so that the plain reference
(``references/<family>.py``) takes nothing the program made.  The tree
has the layout of the served model's parameters (its leaf names and
shapes); the values come from ``--seed`` alone:

* matrices: normal, scaled by 1/sqrt(fan-in), fan-in being the
  second-to-last axis, and the model width for the embedding (which
  the tied head reads transposed), so logits have unit scale;
* norm gains, by name: every leaf named ``ln*`` (``ln1``, ``ln2``,
  ``ln_f``) or ``*norm`` (``q_norm``, ``k_norm``, ``kv_norm``,
  ``ssm_norm``): 1 + 0.1 * normal, so a kernel that drops or misplaces
  a gain shows;
* biases (``bq``, ``bk``, ``bv``): 0.1 * normal.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

BIASES = ("bq", "bk", "bv")


def key_of(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size (``jax.random.key`` keeps only
    32 bits)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def is_gain(name: str) -> bool:
    return name.startswith("ln") or name.endswith("norm")


def _leaf(key, path, shape, dtype):
    name = str(getattr(path[-1], "key", path[-1]))
    if is_gain(name):
        return (1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
                ).astype(dtype)
    if name in BIASES:
        return (0.1 * jax.random.normal(key, shape, jnp.float32)).astype(dtype)
    # the embedding (vocab, d) is the tied head's transpose: fan-in d
    scale = (shape[-1] if name == "embed" else shape[-2]) ** -0.5
    return (scale * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def make(like, seed: int):
    """A tree shaped as ``like`` (arrays or ShapeDtypeStructs) with seeded
    values, built by one jitted call on the default device."""
    shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                          like)
    leaves = jax.tree_util.tree_flatten_with_path(shapes)[0]
    treedef = jax.tree.structure(shapes)

    @jax.jit
    def build(key):
        out = []
        for i, (path, s) in enumerate(leaves):
            out.append(_leaf(jax.random.fold_in(key, i), path, s.shape,
                             s.dtype))
        return jax.tree.unflatten(treedef, out)

    return build(key_of(seed))
