"""Weights from the seed, named the way the reference reads them.

Both sides make the same numbers from the seed: the program gets them in the
type it serves (one jitted call, on the device), and the reference makes each
layer's again in float32 when it reaches that layer.  Each tensor is uniform
with the standard deviation of the usual fan-in initialisation, drawn from
its own key (layer, tensor), and rounded to the served type before either
side sees it, so the reference computes with the served values.

The model dict holds Hugging Face key names (``hidden_size``, ...), as the
configuration files under ``bench/configs`` state them.
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


def is_moe(m: dict) -> bool:
    return m["num_local_experts"] > 0


def layer_shapes(m: dict) -> dict[str, tuple[tuple[int, ...], float]]:
    """(shape, std) of each tensor of one layer; std 0 means ones (norms)."""
    d, hd = m["hidden_size"], m["head_dim"]
    h, hkv, ff = m["num_attention_heads"], m["num_key_value_heads"], \
        m["intermediate_size"]
    s = {
        "attn_norm": ((d,), 0.0),
        "wq": ((d, h * hd), d ** -0.5),
        "wk": ((d, hkv * hd), d ** -0.5),
        "wv": ((d, hkv * hd), d ** -0.5),
        "wo": ((h * hd, d), (h * hd) ** -0.5),
        "mlp_norm": ((d,), 0.0),
    }
    if is_moe(m):
        e = m["num_local_experts"]
        s.update({"router": ((d, e), d ** -0.5),
                  "e_gate": ((e, d, ff), d ** -0.5),
                  "e_up": ((e, d, ff), d ** -0.5),
                  "e_down": ((e, ff, d), ff ** -0.5)})
    else:
        s.update({"w_gate": ((d, ff), d ** -0.5),
                  "w_up": ((d, ff), d ** -0.5),
                  "w_down": ((ff, d), ff ** -0.5)})
    return s


def global_shapes(m: dict) -> dict[str, tuple[tuple[int, ...], float]]:
    d, v = m["hidden_size"], m["vocab_size"]
    s = {"embed": ((v, d), EMBED_STD), "final_norm": ((d,), 0.0)}
    if not m["tie_word_embeddings"]:
        s["lm_head"] = ((d, v), EMBED_STD)
    return s


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


def layer_weights(m: dict, key: jax.Array, layer, served, dtype) -> dict:
    """Layer ``layer``'s tensors (``layer`` may be traced, e.g. under vmap)."""
    return _make(jax.random.fold_in(key, layer + 1), layer_shapes(m), served,
                 dtype)


def global_weights(m: dict, key: jax.Array, served, dtype) -> dict:
    return _make(jax.random.fold_in(key, 0), global_shapes(m), served, dtype)
