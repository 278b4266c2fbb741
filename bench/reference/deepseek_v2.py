"""Plain float32 forward pass of a DeepSeek-V2 decoder, one device's share of
its routed experts.

Written from the published description (arXiv:2405.04434 and the model's
own ``modeling_deepseek.py`` on Hugging Face), independent of ``src/repro``:

* pre-norm RMSNorm blocks; a final RMSNorm and an untied LM head;
* multi-head latent attention, expanded: the query through its low-rank
  path (``q_a``, RMSNorm, ``q_b``), the normed latent ``c_kv`` expanded by
  ``kv_b`` into each head's ``k_nope`` and value, one rotary key shared by
  the heads; no weight absorption and no cache;
* rotary positions in Hugging Face's layout: the rotary columns of the
  query and of the shared key are de-interleaved (``view(d/2, 2)``,
  transposed) before rotate-half;
* YaRN: inverse frequencies blended between theta^(-2i/d) / factor and
  theta^(-2i/d) over the linear ramp of the correction range, cos and sin
  times mscale(mscale) / mscale(mscale_all_dim), the softmax scale
  (nope + rope)^-0.5 times mscale(mscale_all_dim)^2;
* the first ``first_k_dense_replace`` layers with a dense SiLU GLU MLP;
* the others with a mixture: softmax over all ``router_experts``, the
  ``topk_group`` groups with the best single score kept and the rest
  zeroed, the top ``num_experts_per_tok``, gates not renormalised and
  multiplied by ``routed_scaling_factor``; of the routed sum only the
  experts held here (``first_held_expert`` and the ``n_routed_experts``
  after it) are computed; plus the shared experts, one GLU of
  ``n_shared_experts x moe_intermediate_size``.

Departures: attention runs in query blocks, so that 128 heads fit at
thousands of positions; the routed experts held elsewhere are left out, as
they are on the device (their part of the sum comes from other chips).

Every matmul runs at "highest" precision.  Weights come from
``bench.weights``, layer by layer, with the tensors that
``bench/arch/deepseek_v2.py`` names.  ``fp8=True`` is the control: every
linear layer's operands, weights (per output column) and activations (per
row), are rounded to float8 e4m3 before the matmul.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W
from bench.reference.llama import _glu, _mm, _rms

Q_BLOCK = 512  # query rows per attention block


def _yarn_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def rope_tables(m: dict, n: int) -> tuple[np.ndarray, np.ndarray]:
    """cos and sin (n, rope) of positions 0..n-1, as the model's YaRN
    rotary embedding caches them."""
    dim, base = m["qk_rope_head_dim"], m["rope_theta"]
    rs = m["rope_scaling"]
    factor = rs["factor"]
    pairs = np.arange(0, dim, 2, dtype=np.float32) / dim
    freq_extra = 1.0 / base ** pairs
    freq_inter = 1.0 / (factor * base ** pairs)

    def corr_dim(rotations):
        return (dim * math.log(rs["original_max_position_embeddings"]
                               / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(corr_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(corr_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    mask = 1.0 - ramp
    inv_freq = freq_inter * (1 - mask) + freq_extra * mask
    freqs = np.outer(np.arange(n, dtype=np.float32), inv_freq)
    emb = np.concatenate([freqs, freqs], -1)
    mag = (_yarn_mscale(factor, rs["mscale"])
           / _yarn_mscale(factor, rs["mscale_all_dim"]))
    return (np.cos(emb) * mag).astype(np.float32), \
        (np.sin(emb) * mag).astype(np.float32)


def softmax_scale(m: dict) -> float:
    scale = (m["qk_nope_head_dim"] + m["qk_rope_head_dim"]) ** -0.5
    rs = m["rope_scaling"]
    if rs.get("mscale_all_dim"):
        mscale = _yarn_mscale(rs["factor"], rs["mscale_all_dim"])
        scale = scale * mscale * mscale
    return scale


def _rope(x, cos, sin):
    """x (S, heads, d) in the checkpoint's column order."""
    s, h, d = x.shape
    x = x.reshape(s, h, d // 2, 2).swapaxes(-1, -2).reshape(s, h, d)
    rot = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], -1)
    return x * cos[:, None] + rot * sin[:, None]


def _attention(m: dict, fp8: bool, a, w, cos, sin):
    s = a.shape[0]
    h, nope, rope, vd, kl = (m["num_attention_heads"], m["qk_nope_head_dim"],
                             m["qk_rope_head_dim"], m["v_head_dim"],
                             m["kv_lora_rank"])
    eps = m["rms_norm_eps"]
    q = _mm(_rms(_mm(a, w["q_a"], fp8), w["q_a_norm"], eps), w["q_b"], fp8)
    q = q.reshape(s, h, nope + rope)
    ckv = _mm(a, w["kv_a"], fp8)  # (S, kv_lora + rope)
    k_pe = _rope(ckv[:, None, kl:], cos, sin)  # (S, 1, rope)
    kv = _mm(_rms(ckv[:, :kl], w["kv_a_norm"], eps), w["kv_b"], fp8)
    kv = kv.reshape(s, h, nope + vd)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_pe, (s, h, rope))], -1)
    v = kv[..., nope:]
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], cos, sin)], -1)
    scale = softmax_scale(m)
    qb = min(Q_BLOCK, s)
    n_blocks = -(-s // qb)
    q = jnp.pad(q, ((0, n_blocks * qb - s), (0, 0), (0, 0)))
    keys = np.arange(s)

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb)
        scores = jnp.einsum("qhd,khd->hqk", qi, k) * scale
        rows = i * qb + jnp.arange(qb)
        causal = keys[None, :] <= rows[:, None]
        p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    o = jax.lax.map(block, jnp.arange(n_blocks)).reshape(-1, h, vd)[:s]
    return _mm(o.reshape(s, h * vd), w["o"], fp8)


def route(m: dict, probs):
    """Gates (S, router_experts) of DeepSeek-V2's group-limited greedy
    top-k over softmax scores ``probs``."""
    s, e = probs.shape
    g = m["n_group"]
    group_best = probs.reshape(s, g, e // g).max(-1)
    _, top_groups = jax.lax.top_k(group_best, m["topk_group"])
    group_mask = jnp.zeros((s, g)).at[jnp.arange(s)[:, None],
                                      top_groups].set(1.0)
    score_mask = jnp.repeat(group_mask, e // g, axis=1)
    top, idx = jax.lax.top_k(jnp.where(score_mask > 0, probs, 0.0),
                             m["num_experts_per_tok"])
    if m["norm_topk_prob"]:
        top = top / (jnp.sum(top, -1, keepdims=True) + 1e-20)
    else:
        top = top * m["routed_scaling_factor"]
    return jnp.zeros((s, e)).at[jnp.arange(s)[:, None], idx].set(top)


def _moe(m: dict, fp8: bool, a, w):
    gates = route(m, jax.nn.softmax(_mm(a, w["router"], fp8), axis=-1))
    first = m["first_held_expert"]
    y = _glu(a, w["s_gate"], w["s_up"], w["s_down"], fp8)
    for i in range(m["n_routed_experts"]):
        y = y + gates[:, first + i:first + i + 1] * _glu(
            a, w["e_gate"][i], w["e_up"][i], w["e_down"][i], fp8)
    return y


def _block(m: dict, fp8: bool, dense: bool, x, w, cos, sin):
    eps = m["rms_norm_eps"]
    x = x + _attention(m, fp8, _rms(x, w["attn_norm"], eps), w, cos, sin)
    a = _rms(x, w["mlp_norm"], eps)
    if dense:
        return x + _glu(a, w["w_gate"], w["w_up"], w["w_down"], fp8)
    return x + _moe(m, fp8, a, w)


class Reference:
    """Forward passes of one configuration with one seed's weights;
    ``layer_shapes(m, dense)`` and ``global_shapes(m)`` describe the
    tensors."""

    def __init__(self, m: dict, seed: int, served_dtype: str,
                 layer_shapes, global_shapes: dict):
        self.m = m
        self.key = W.seed_key(seed)
        served = jnp.dtype(served_dtype)
        self._layer = {dense: jax.jit(functools.partial(
            W.layer_weights, layer_shapes(m, dense), served=served,
            dtype=jnp.float32)) for dense in (False, True)}
        self._globals = jax.jit(functools.partial(
            W.global_weights, global_shapes, served=served,
            dtype=jnp.float32))
        self._blocks = {(fp8, dense): jax.jit(functools.partial(
            _block, m, fp8, dense)) for fp8 in (False, True)
            for dense in (False, True)}
        self._heads = {fp8: jax.jit(functools.partial(self._head_impl, fp8))
                       for fp8 in (False, True)}

    def _head_impl(self, fp8, x, g, rows):
        x = _rms(x[rows], g["final_norm"], self.m["rms_norm_eps"])
        return _mm(x, g["lm_head"], fp8)

    def logits(self, tokens: np.ndarray, rows: np.ndarray,
               fp8: bool = False) -> jax.Array:
        """Logits (len(rows), vocab) at positions ``rows`` of ``tokens``.

        Sequences of one length share compiled programs: pad ``tokens`` at
        the end (causal attention keeps earlier positions exact)."""
        cos, sin = (jnp.asarray(t) for t in rope_tables(self.m, len(tokens)))
        with jax.default_matmul_precision("highest"):
            g = self._globals(self.key)
            x = g["embed"][jnp.asarray(tokens)]
            for layer in range(self.m["num_hidden_layers"]):
                dense = layer < self.m["first_k_dense_replace"]
                x = self._blocks[fp8, dense](
                    x, self._layer[dense](self.key, layer), cos, sin)
            return self._heads[fp8](x, g, jnp.asarray(rows))
