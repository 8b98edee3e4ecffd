"""Weights from the seed, made on the device in one jitted call.

A reference module's `param_spec` names every leaf with its shape and kind;
this draws them all in one program: matrices and biases ~ N(0, 0.02) (the
configurations' `initializer_range`), LayerNorm scales ~ 1 + N(0, 0.02).
Biases and scales are drawn, not left at 0 and 1, so that a bias or a scale
dropped from the step shows in the comparison.  The same call serves the
timed path and, later in the run, the reference: the reference takes
nothing the program made.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

STD = 0.02


def seed_key(seed: int, stream: int = 0):
    """A threefry key from any non-negative whole number (the driver's
    seeds pass 2**31): the seed is spread over the key's two words by
    numpy's SeedSequence, never squeezed through an int32."""
    words = np.random.SeedSequence([int(seed), stream]).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


def _draw(spec, key):
    leaves, treedef = jax.tree_util.tree_flatten(
        spec, is_leaf=lambda x: isinstance(x, tuple))
    out = []
    for i, (shape, kind) in enumerate(leaves):
        x = STD * jax.random.normal(jax.random.fold_in(key, i), shape,
                                    jnp.float32)
        out.append(1.0 + x if kind == "scale" else x)
    return jax.tree_util.tree_unflatten(treedef, out)


def shapes(spec):
    return jax.tree_util.tree_map(
        lambda leaf: jax.ShapeDtypeStruct(leaf[0], jnp.float32), spec,
        is_leaf=lambda x: isinstance(x, tuple))


def maker(spec, shardings=None):
    """jitted `key -> params`; `shardings` is a tree like the spec or one
    sharding for every leaf."""
    return jax.jit(lambda key: _draw(spec, key), out_shardings=shardings)
