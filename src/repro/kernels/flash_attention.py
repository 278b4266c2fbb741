"""Flash attention for TPU: fused streaming-softmax with BlockSpec VMEM tiling.

Adaptation notes (DESIGN.md §6): FlashAttention's GPU formulation (warps,
shared-memory tiles) is re-expressed for the TPU memory hierarchy — HBM ->
VMEM block copies driven by ``pl.BlockSpec`` index maps, (block_q x block_k)
score tiles shaped for the 128x128 MXU, and the online max/denominator carry
kept in VMEM scratch across the sequential kv grid dimension.  Causal and
sliding-window blocks that are fully masked are skipped via ``pl.when``
(the TPU grid is sequential in the innermost dimension, so the skip saves
real MXU cycles rather than relying on SM occupancy).

Supports GQA/MQA directly: kv blocks are indexed by q_head // group_size.
Positions are contiguous (pos_q = q_offset + iota, pos_k = iota) — the
train/prefill regime; decode uses the XLA path (attention.py), where the
work per step is tiny.

Arbitrary sequence lengths are supported: inputs are padded up to the next
block multiple and the output sliced back.  Padded key positions are masked
inside the kernel via ``kv_len`` (for causal attention with the standard
``q_offset = Skv - Sq`` continuation layout the causal mask already excludes
them, but the explicit mask keeps bidirectional and window variants correct
too).  When the shapes already divide the blocks, the raw unpadded path runs
unchanged.

The epilogue (``out_scale`` multiply + ``residual`` add) is fused into the
final kv step's ``_finish`` so the scaled/residual-added output leaves VMEM
exactly once instead of costing an extra HBM round trip.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _flash_kernel(q_ref, k_ref, v_ref, *rest, scale: float, causal: bool,
                  window: int, q_offset: int, kv_len: int, block_q: int,
                  block_k: int, n_kv_blocks: int, out_scale: float,
                  has_residual: bool, precision):
    if has_residual:
        res_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        res_ref = None
        o_ref, acc_ref, m_ref, l_ref = rest
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = q_offset + iq * block_q
    k_start = ik * block_k
    masked = causal or window > 0 or kv_len > 0

    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)  # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                precision=precision,
                                preferred_element_type=jnp.float32) * scale

        if masked:
            pos_q = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            pos_k = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            mask = jnp.ones_like(s, dtype=jnp.bool_)
            if causal:
                mask &= pos_k <= pos_q
            if window > 0:
                mask &= (pos_q - pos_k) < window
            if kv_len > 0:  # padded keys beyond the true length
                mask &= pos_k < kv_len
            s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, 0]
        l_prev = l_ref[:, 0]
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)
        acc_ref[...] = (acc_ref[...] * alpha[:, None]
                        + jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                              precision=precision,
                                              preferred_element_type=jnp.float32))
        m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    if masked:
        # Block-level skip: entirely-future (causal), stale (window) or
        # fully-padded (kv_len) tiles.
        should = jnp.bool_(True)
        if causal:
            should &= q_start + block_q - 1 >= k_start
        if window > 0:
            should &= q_start - (k_start + block_k - 1) < window
        if kv_len > 0:
            should &= k_start < kv_len
        pl.when(should)(_compute)
    else:
        _compute()

    @pl.when(ik == n_kv_blocks - 1)
    def _finish():
        l = l_ref[:, 0]
        l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> 0 output
        o = acc_ref[...] / l[:, None]
        if out_scale != 1.0:
            o = o * out_scale
        if res_ref is not None:
            o = o + res_ref[0, 0].astype(jnp.float32)
        o_ref[0, 0] = o.astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,  # (B, Sq, Hq, D)
    k: jax.Array,  # (B, Skv, Hkv, D)
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    scale: float | None = None,
    q_offset: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    out_scale: float = 1.0,
    residual: jax.Array | None = None,  # (B, Sq, Hq, Dv), fused epilogue add
    interpret: bool = False,
) -> jax.Array:
    B, Sq, Hq, D = q.shape
    _, Skv, Hkv, Dv = v.shape
    G = Hq // Hkv
    scale = scale if scale is not None else D ** -0.5
    block_q = min(block_q, Sq)
    block_k = min(block_k, Skv)

    # pad-to-block / slice-back: arbitrary sequence lengths run through the
    # same kernel; the raw path below is untouched when shapes divide
    pad_q = -Sq % block_q
    pad_k = -Skv % block_k
    kv_len = Skv if pad_k else 0
    if pad_q or pad_k:
        if pad_q:
            q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
            if residual is not None:
                residual = jnp.pad(residual,
                                   ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        if pad_k:
            k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        Sq_p, Skv_p = Sq + pad_q, Skv + pad_k
    else:
        Sq_p, Skv_p = Sq, Skv
    nq, nk = Sq_p // block_q, Skv_p // block_k

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        q_offset=q_offset, kv_len=kv_len, block_q=block_q, block_k=block_k,
        n_kv_blocks=nk, out_scale=out_scale,
        has_residual=residual is not None,
        # f32 inputs get f32 matmuls: Mosaic's default rounds f32 operands
        # to bf16 (1e-2 errors on a v5e); bf16 inputs lose nothing by it
        precision=(jax.lax.Precision.HIGHEST if q.dtype == jnp.float32
                   else None))

    # head-major inside the wrapper: every block's last two dims are then
    # (block, D) -- the (8, 128)-tileable layout Mosaic requires -- instead of
    # (1 head, D) slices of the (B, S, H, D) interface layout
    q, k, v = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    q_spec = pl.BlockSpec((1, 1, block_q, D), lambda b, h, iq, ik: (b, h, iq, 0))
    in_specs = [
        q_spec,
        pl.BlockSpec((1, 1, block_k, D), lambda b, h, iq, ik: (b, h // G, ik, 0)),
        pl.BlockSpec((1, 1, block_k, Dv), lambda b, h, iq, ik: (b, h // G, ik, 0)),
    ]
    o_spec = pl.BlockSpec((1, 1, block_q, Dv), lambda b, h, iq, ik: (b, h, iq, 0))
    operands = [q, k, v]
    if residual is not None:
        in_specs.append(o_spec)
        operands.append(residual.transpose(0, 2, 1, 3))

    out = pl.pallas_call(
        kernel,
        grid=(B, Hq, nq, nk),
        in_specs=in_specs,
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq_p, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, Dv), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),  # row-max, lane-broadcast
            pltpu.VMEM((block_q, 128), jnp.float32),  # row-sum, lane-broadcast
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*operands)
    out = out.transpose(0, 2, 1, 3)
    if pad_q:
        out = out[:, :Sq]
    return out
