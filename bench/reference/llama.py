"""Plain float32 forward pass of a Llama-style decoder, dense GLU or top-k MoE.

Written from the published description, independent of ``src/repro``:
pre-norm RMSNorm blocks, rotary positions (split-half), grouped-query causal
attention, a SiLU GLU MLP or a softmax top-k mixture of GLU experts with the
chosen gates renormalised, a final RMSNorm and an LM head (tied or not).
Every matmul runs at "highest" precision.  Weights come from
``bench.weights``, layer by layer, so the whole model never has to be
resident in float32, with the tensors that ``bench/arch/llama.py`` names.

``fp8=True`` is the control: every linear layer's operands, weights (per
output column) and activations (per row), are rounded to float8 e4m3 before
the matmul, as a float8 serving path would compute them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

_F8_MAX = 448.0  # largest finite float8 e4m3


def _q8(t: jax.Array, axis: int) -> jax.Array:
    s = jnp.max(jnp.abs(t), axis=axis, keepdims=True) / _F8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (t / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x: jax.Array, w: jax.Array, fp8: bool) -> jax.Array:
    """x (..., k) @ w (k, n)."""
    if fp8:
        x, w = _q8(x, -1), _q8(w, -2)
    return x @ w


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x (S, heads, hd); positions 0..S-1; halves rotated as pairs."""
    s, _, hd = x.shape
    inv = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd)
    ang = np.arange(s, dtype=np.float32)[:, None] * inv  # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _glu(x, w_gate, w_up, w_down, fp8):
    return _mm(jax.nn.silu(_mm(x, w_gate, fp8)) * _mm(x, w_up, fp8), w_down,
               fp8)


def _block(m: dict, fp8: bool, x: jax.Array, w: dict) -> jax.Array:
    s = x.shape[0]
    h, hkv, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                  m["head_dim"])
    eps = m["rms_norm_eps"]

    a = _rms(x, w["attn_norm"], eps)
    q = _rope(_mm(a, w["wq"], fp8).reshape(s, h, hd), m["rope_theta"])
    k = _rope(_mm(a, w["wk"], fp8).reshape(s, hkv, hd), m["rope_theta"])
    v = _mm(a, w["wv"], fp8).reshape(s, hkv, hd)
    # query head i reads key/value head i // (h / hkv)
    k = jnp.repeat(k, h // hkv, axis=1)
    v = jnp.repeat(v, h // hkv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(hd)
    causal = np.tril(np.ones((s, s), bool))
    p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v).reshape(s, h * hd)
    x = x + _mm(o, w["wo"], fp8)

    a = _rms(x, w["mlp_norm"], eps)
    if not m["num_local_experts"]:
        return x + _glu(a, w["w_gate"], w["w_up"], w["w_down"], fp8)
    e, k_top = m["num_local_experts"], m["num_experts_per_tok"]
    probs = jax.nn.softmax(_mm(a, w["router"], fp8), axis=-1)  # (S, E)
    top, idx = jax.lax.top_k(probs, k_top)
    top = top / jnp.sum(top, -1, keepdims=True)
    gates = jnp.zeros((s, e)).at[jnp.arange(s)[:, None], idx].set(top)
    y = jnp.zeros_like(x)
    for i in range(e):
        y = y + gates[:, i:i + 1] * _glu(a, w["e_gate"][i], w["e_up"][i],
                                         w["e_down"][i], fp8)
    return x + y


class Reference:
    """Forward passes of one configuration with one seed's weights, of the
    tensors ``layer_shapes`` and ``global_shapes`` describe."""

    def __init__(self, m: dict, seed: int, served_dtype: str,
                 layer_shapes: dict, global_shapes: dict):
        self.m = m
        self.key = W.seed_key(seed)
        served = jnp.dtype(served_dtype)
        self._layer = jax.jit(functools.partial(
            W.layer_weights, layer_shapes, served=served, dtype=jnp.float32))
        self._globals = jax.jit(functools.partial(
            W.global_weights, global_shapes, served=served,
            dtype=jnp.float32))
        self._blocks = {fp8: jax.jit(functools.partial(_block, m, fp8))
                        for fp8 in (False, True)}
        self._heads = {fp8: jax.jit(functools.partial(self._head_impl, fp8))
                       for fp8 in (False, True)}

    def _head_impl(self, fp8, x, g, rows):
        x = _rms(x[rows], g["final_norm"], self.m["rms_norm_eps"])
        head = g["embed"].T if self.m["tie_word_embeddings"] else g["lm_head"]
        return _mm(x, head, fp8)

    def logits(self, tokens: np.ndarray, rows: np.ndarray,
               fp8: bool = False) -> jax.Array:
        """Logits (len(rows), vocab) at positions ``rows`` of ``tokens``.

        Sequences of one length share compiled programs: pad ``tokens`` at
        the end (causal attention keeps earlier positions exact)."""
        with jax.default_matmul_precision("highest"):
            g = self._globals(self.key)
            x = g["embed"][jnp.asarray(tokens)]
            for layer in range(self.m["num_hidden_layers"]):
                x = self._blocks[fp8](x, self._layer(self.key, layer))
            return self._heads[fp8](x, g, jnp.asarray(rows))
