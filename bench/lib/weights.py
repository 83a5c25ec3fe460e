"""Weights of a configuration, made on the device from the seed.

One jitted call draws every matrix as a normal of std
``initializer_range`` (the published initialisation), RMSNorm weights 1,
and rounds them to the type they are run in.  The shapes and the names
of the leaves that stay float32 (``NORMS``) are the reference model's
(``bench/reference/<name>.py``).  The benchmark gives the program these
weights (through the family's adapter), and the reference draws the same
ones again from the same seed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib import program


def key_of(seed: int):
    """A PRNG key from any whole number (wider than 32 bits too)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


@functools.lru_cache(maxsize=None)
def _maker(conf_key: tuple, dtype: str):
    conf = dict(conf_key)
    ref = program.reference(conf)
    tree = ref.shapes(conf)
    std = conf["initializer_range"]
    leaves, treedef = jax.tree_util.tree_flatten(
        tree, is_leaf=lambda x: isinstance(x, tuple))
    paths = [p for p, _ in jax.tree_util.tree_leaves_with_path(
        tree, is_leaf=lambda x: isinstance(x, tuple))]
    names = [str(getattr(p[-1], "key", p[-1])) for p in paths]

    def make(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, shape, name in zip(keys, leaves, names):
            if name in ref.NORMS:
                out.append(jnp.ones(shape, jnp.float32))
            else:
                w = std * jax.random.normal(k, shape, jnp.float32)
                out.append(w.astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(make)


def make(conf: dict, seed: int, dtype: str):
    """The weight tree of ``conf`` for ``seed``, matrices in ``dtype`` and
    RMSNorm weights in float32."""
    key = tuple(sorted((k, v) for k, v in conf.items()
                       if isinstance(v, (int, float, str))))
    return _maker(key, dtype)(key_of(seed))


def as_f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def hold(conf: dict, tree, dtype=None):
    """``tree`` as the configuration holds its weights between steps:
    every leaf but the reference's ``NORMS`` rounded to ``dtype`` (the
    configuration's type unless given), returned as float32."""
    norms = program.reference(conf).NORMS
    dtype = dtype or conf["torch_dtype"]

    def h(path, leaf):
        name = str(getattr(path[-1], "key", path[-1]))
        return leaf if name in norms else leaf.astype(dtype).astype(
            jnp.float32)
    return jax.tree_util.tree_map_with_path(h, tree)
