"""Weights from the seed, named the way the reference reads them.

Both sides make the same numbers from the seed: the program gets them in the
type it serves (one jitted call, on the device), and the reference makes each
layer's again in float32 when it reaches that layer.  Each tensor is uniform
with the standard deviation of the usual fan-in initialisation, drawn from
its own key (layer, tensor), and rounded to the served type before either
side sees it, so the reference computes with the served values.

Which tensors a layer and the model hold, with their shapes and standard
deviations, is the architecture's (``layer_shapes`` and ``global_shapes`` of
``bench/arch/<name>.py``); the tensors are made in the order of their names.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

EMBED_STD = 0.02  # embedding and untied LM head


def seed_key(seed: int) -> jax.Array:
    """A key from any whole number: both 32-bit halves are folded in."""
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32((seed >> 32) & 0xFFFFFFFF))


def _make(key, shapes, served, dtype):
    out = {}
    for i, (name, (shape, std)) in enumerate(sorted(shapes.items())):
        if std == 0.0:
            out[name] = jnp.ones(shape, dtype)
            continue
        a = std * math.sqrt(3.0)
        w = jax.random.uniform(jax.random.fold_in(key, i), shape, jnp.float32,
                               -a, a)
        out[name] = w.astype(served).astype(dtype)
    return out


def layer_weights(shapes: dict, key: jax.Array, layer, served,
                  dtype) -> dict:
    """Layer ``layer``'s tensors (``layer`` may be traced, e.g. under vmap),
    of the architecture's ``layer_shapes``."""
    return _make(jax.random.fold_in(key, layer + 1), shapes, served, dtype)


def global_weights(shapes: dict, key: jax.Array, served, dtype) -> dict:
    """The tensors outside the layers, of the architecture's
    ``global_shapes``."""
    return _make(jax.random.fold_in(key, 0), shapes, served, dtype)
