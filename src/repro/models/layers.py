"""Shared layers: param plumbing, norms, rotary embeddings, MLPs.

Parameters are created as ``Param(value, axes)`` where ``axes`` names the
logical sharding axis of every dim (see distributed/sharding.py).  ``split``
separates the value tree from the axes tree; model code then works with plain
array pytrees, and the axes tree drives NamedSharding construction in the
launcher.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig


@dataclasses.dataclass
class Param:
    value: Any  # array when initializing, ShapeDtypeStruct when abstract
    axes: tuple[str | None, ...]

    def __post_init__(self) -> None:
        assert len(self.axes) == len(self.value.shape), (self.axes, self.value.shape)


def is_param(x: Any) -> bool:
    return isinstance(x, Param)


def split(tree: Any) -> tuple[Any, Any]:
    """(values, axes) from a tree whose leaves are Param."""
    values = jax.tree.map(lambda p: p.value, tree, is_leaf=is_param)
    axes = jax.tree.map(lambda p: tuple(p.axes), tree, is_leaf=is_param)
    return values, axes


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------


def dense_init(key: jax.Array, shape: tuple[int, ...], in_dims: int, dtype: str) -> jax.Array:
    """Truncated-normal fan-in init (LeCun-style), robust across widths."""
    fan_in = max(1, int(np.prod(shape[:in_dims])))
    std = 1.0 / np.sqrt(fan_in)
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) * std).astype(dtype)


def embed_init(key: jax.Array, shape: tuple[int, ...], dtype: str) -> jax.Array:
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def init_norm(cfg: ModelConfig, d: int) -> dict:
    p = {"scale": Param(jnp.zeros((d,), cfg.param_dtype) if cfg.gemma_norm
                        else jnp.ones((d,), cfg.param_dtype), (None,))}
    if cfg.norm_type == "layernorm":
        p["bias"] = Param(jnp.zeros((d,), cfg.param_dtype), (None,))
    return p


def apply_norm(p: dict, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    if cfg.norm_type == "layernorm":
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.var(x, axis=-1, keepdims=True)
        x_hat = (x - mean) * jax.lax.rsqrt(var + cfg.norm_eps)
        out = x_hat * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:  # rmsnorm
        ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        x_hat = x * jax.lax.rsqrt(ms + cfg.norm_eps)
        scale = p["scale"].astype(jnp.float32)
        if cfg.gemma_norm:
            scale = 1.0 + scale
        out = x_hat * scale
    return out.astype(dt)


# ---------------------------------------------------------------------------
# Positions: RoPE / M-RoPE / sinusoidal
# ---------------------------------------------------------------------------


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's magnitude correction for a context stretched ``factor`` times."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_correction_range(cfg: ModelConfig, rot_dim: int) -> tuple[int, int]:
    """The frequency pairs [low, high] over which YaRN ramps from the plain
    frequencies to the interpolated ones: those that turn beta_fast to
    beta_slow times over the original context."""
    def dim(rotations: float) -> float:
        return (rot_dim * math.log(cfg.yarn_original_max_pos
                                   / (rotations * 2 * math.pi))
                / (2 * math.log(cfg.rope_theta)))

    low = math.floor(dim(cfg.yarn_beta_fast))
    high = math.ceil(dim(cfg.yarn_beta_slow))
    return max(low, 0), min(high, rot_dim - 1)


def rope_freqs(cfg: ModelConfig, rot_dim: int) -> jax.Array:
    """Inverse frequencies for the rotated sub-dimension (pairs = rot_dim/2).
    Under YaRN, pair i blends theta^(-2i/d) / factor (weight ramp_i) with
    theta^(-2i/d) (weight 1 - ramp_i), ramp_i linear from 0 at ``low`` to 1
    at ``high`` of the correction range."""
    exponent = jnp.arange(0, rot_dim, 2, dtype=jnp.float32) / rot_dim
    plain = 1.0 / (cfg.rope_theta ** exponent)  # (rot_dim/2,)
    if cfg.yarn_factor <= 1:
        return plain
    low, high = yarn_correction_range(cfg, rot_dim)
    ramp = jnp.clip((jnp.arange(rot_dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return plain / cfg.yarn_factor * ramp + plain * (1.0 - ramp)


def rope_magnitude(cfg: ModelConfig) -> float:
    """The factor YaRN puts on cos and sin (1 without YaRN)."""
    return (yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale)
            / yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim))


def apply_rope(x: jax.Array, positions: jax.Array, cfg: ModelConfig,
               rot_dim: int | None = None) -> jax.Array:
    """Rotary embedding, split-half (NeoX) convention.

    x: (..., seq, heads, head_dim); positions: (batch, seq) int32 or
    (3, batch, seq) for M-RoPE (temporal/height/width coordinates).
    """
    head_dim = x.shape[-1]
    rot = rot_dim if rot_dim is not None else int(head_dim * cfg.rope_fraction)
    rot = min(rot, head_dim)
    inv_freq = rope_freqs(cfg, rot)  # (rot/2,)

    if cfg.pos_type == "mrope":
        assert positions.ndim == 3, "mrope needs (3, batch, seq) positions"
        sections = cfg.mrope_sections  # in freq pairs, sums to rot/2
        assert sum(sections) == rot // 2, (sections, rot)
        # angle per pair selected from the section's coordinate stream
        angles = []
        start = 0
        for comp, sec in enumerate(sections):
            f = inv_freq[start : start + sec]  # (sec,)
            pos = positions[comp].astype(jnp.float32)  # (b, s)
            angles.append(pos[..., None] * f)  # (b, s, sec)
            start += sec
        angle = jnp.concatenate(angles, axis=-1)  # (b, s, rot/2)
    else:
        angle = positions.astype(jnp.float32)[..., None] * inv_freq  # (b, s, rot/2)

    sin = jnp.sin(angle)[..., None, :]  # (b, s, 1, rot/2)
    cos = jnp.cos(angle)[..., None, :]
    mag = rope_magnitude(cfg)
    if mag != 1.0:
        sin, cos = sin * mag, cos * mag
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    x1, x2 = x_rot[..., : rot // 2], x_rot[..., rot // 2 :]
    out1 = (x1.astype(jnp.float32) * cos - x2.astype(jnp.float32) * sin)
    out2 = (x2.astype(jnp.float32) * cos + x1.astype(jnp.float32) * sin)
    out = jnp.concatenate([out1.astype(x.dtype), out2.astype(x.dtype)], axis=-1)
    if x_pass.shape[-1]:
        out = jnp.concatenate([out, x_pass], axis=-1)
    return out


def sinusoidal_positions(seq_len: int, d_model: int, dtype=jnp.float32) -> jax.Array:
    """Standard transformer sinusoids (whisper encoder positions)."""
    pos = np.arange(seq_len)[:, None]
    dim = np.arange(d_model // 2)[None, :]
    angle = pos / np.power(10000.0, 2 * dim / d_model)
    table = np.concatenate([np.sin(angle), np.cos(angle)], axis=-1)
    return jnp.asarray(table, dtype=dtype)


# ---------------------------------------------------------------------------
# MLP (dense / GLU)
# ---------------------------------------------------------------------------


def init_mlp(key: jax.Array, cfg: ModelConfig, d_ff: int | None = None) -> dict:
    d, ff = cfg.d_model, d_ff or cfg.d_ff
    dt = cfg.param_dtype
    ks = jax.random.split(key, 3)
    p = {
        "w_up": Param(dense_init(ks[0], (d, ff), 1, dt), ("embed_fsdp", "mlp")),
        "w_down": Param(dense_init(ks[1], (ff, d), 1, dt), ("mlp", "embed_fsdp")),
    }
    if cfg.mlp_type == "glu":
        p["w_gate"] = Param(dense_init(ks[2], (d, ff), 1, dt), ("embed_fsdp", "mlp"))
    return p


def _act(cfg: ModelConfig, x: jax.Array) -> jax.Array:
    if cfg.act == "gelu":
        return jax.nn.gelu(x, approximate=True)
    return jax.nn.silu(x)


def apply_mlp(p: dict, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    cdt = cfg.compute_dtype
    h = x @ p["w_up"].astype(cdt)
    if cfg.mlp_type == "glu":
        g = x @ p["w_gate"].astype(cdt)
        h = _act(cfg, g) * h
    else:
        h = _act(cfg, h)
    return h @ p["w_down"].astype(cdt)
