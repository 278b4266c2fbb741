"""Chunked gated-linear-recurrence kernel (RWKV-6 WKV) for TPU.

The GPU formulations (RWKV CUDA, GLA fused chunk) rely on warp-level
parallelism over heads; the TPU-native shape is: one (batch, head) per
parallel grid cell, the chunk dimension sequential ("arbitrary"), the
running (N x N) state held in VMEM scratch across chunks, and the intra-chunk
part expressed as (C x C) tiles that feed the MXU.  Stability: all decay
algebra happens in log space; every exp() argument is <= 0 by construction.

  y_t = r_t . (S_{t-1} + (u*k_t) v_t^T);   S_t = diag(w_t) S_{t-1} + k_t v_t^T

The u-bonus term is fused into the intra-chunk tile's diagonal (d[t,t,:] = u)
instead of being recomputed as a separate (C,) reduction plus a rank-1 add:
the single (C x C) @ (C x N) MXU matmul then carries both the strict-lower
intra-chunk part and the bonus in one pass.

Arbitrary sequence lengths are supported by zero-padding up to the chunk
multiple: padded steps carry log_w = 0 (decay 1) and k = 0, so the running
state — and therefore ``s_fin`` — passes through them unchanged; the padded
``y`` rows are sliced away.  Shapes that already divide run the raw path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# every matmul at full f32: Mosaic's default rounds f32 operands to bf16,
# which on a v5e put 1e-2 errors into f32 outputs, and the state recurrence
# would carry them from chunk to chunk
_F32 = jax.lax.Precision.HIGHEST

def _wkv_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, s0_ref, y_ref, s_out_ref,
                s_scr, p_scr, *, chunk: int, n_chunks: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        s_scr[...] = s0_ref[0, 0].astype(jnp.float32)

    r = r_ref[0, 0].astype(jnp.float32)  # (C, N)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    lw = lw_ref[0, 0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)  # (1, N)
    S = s_scr[...]

    # inclusive log-decay (<= 0), summed row by row into scratch: Mosaic has
    # no cumsum, and a sequential sum keeps p_prev[t] - p[s] exact over the
    # shared prefix (a matmul prefix sum rounds every row independently)
    def _prefix(t, acc):
        acc = acc + lw_ref[0, 0, pl.ds(t, 1), :].astype(jnp.float32)
        p_scr[pl.ds(t, 1), :] = acc
        return acc

    n = lw.shape[1]
    p_last = jax.lax.fori_loop(0, chunk, _prefix,
                               jnp.zeros((1, n), jnp.float32))  # p[-1]
    p = p_scr[...]
    p_prev = p - lw  # exclusive (through t-1)

    y_inter = jax.lax.dot_general(r * jnp.exp(p_prev), S,
                                  (((1,), (0,)), ((), ())), precision=_F32,
                                  preferred_element_type=jnp.float32)
    # intra-chunk attention-like tile, bonus fused on the diagonal:
    #   A[t,s] = sum_n r[t,n] k[s,n] e^{p_prev[t,n]-p[s,n]}   (s < t)
    #   A[t,t] = sum_n r[t,n] k[t,n] u[n]                     (u-bonus)
    # (C, C, N) masks come from 3-D iotas: Mosaic cannot append a lane dim
    # to a 2-D mask
    row3 = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk, n), 0)
    col3 = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk, n), 1)
    diff = p_prev[:, None, :] - p[None, :, :]  # (C, C, N), masked to s<t
    d = jnp.where(row3 > col3, jnp.exp(jnp.minimum(diff, 0.0)), 0.0)
    d = jnp.where(row3 == col3, u[None], d)
    a = jnp.sum(r[:, None, :] * k[None, :, :] * d, axis=-1)  # (C, C)
    y = y_inter + jax.lax.dot_general(a, v, (((1,), (0,)), ((), ())),
                                      precision=_F32,
                                      preferred_element_type=jnp.float32)
    y_ref[0, 0] = y.astype(y_ref.dtype)

    # the chunk's total decay p[-1], as a row for k_hat and as a column that
    # scales the state's key rows; the column is an exact identity matmul,
    # since Mosaic cannot reshape a row into a column
    k_hat = k * jnp.exp(p_last - p)
    eye = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))
    decay = jax.lax.dot_general(eye.astype(jnp.float32), p_last,
                                (((1,), (1,)), ((), ())), precision=_F32,
                                preferred_element_type=jnp.float32)  # (N, 1)
    s_new = (jnp.exp(decay) * S
             + jax.lax.dot_general(k_hat, v, (((0,), (0,)), ((), ())),
                                   precision=_F32,
                                   preferred_element_type=jnp.float32))
    s_scr[...] = s_new

    @pl.when(ic == n_chunks - 1)
    def _finish():
        s_out_ref[0, 0] = s_new.astype(s_out_ref.dtype)


def linear_scan(
    r: jax.Array,  # (B, S, H, N) f32
    k: jax.Array,
    v: jax.Array,
    log_w: jax.Array,  # (B, S, H, N) f32, <= 0
    u: jax.Array,  # (H, N)
    s0: jax.Array,  # (B, H, N, N) f32
    *,
    chunk: int = 64,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    B, S, H, N = r.shape
    chunk = min(chunk, S)

    # pad-to-chunk / slice-back: zeros in (r, k, v) and log_w = 0 leave the
    # recurrence state untouched, so s_fin stays exact
    pad = -S % chunk
    if pad:
        seq_pad = ((0, 0), (0, pad), (0, 0), (0, 0))
        r = jnp.pad(r, seq_pad)
        k = jnp.pad(k, seq_pad)
        v = jnp.pad(v, seq_pad)
        log_w = jnp.pad(log_w, seq_pad)
    S_p = S + pad
    nc = S_p // chunk

    kernel = functools.partial(_wkv_kernel, chunk=chunk, n_chunks=nc)
    # head-major inside the wrapper, and u as (H, 1, N): every block's last
    # two dims are then (chunk, N), (1, N) or (N, N) -- Mosaic-tileable
    # because each is a multiple of (8, 128) or the array's full extent
    r, k, v, log_w = (x.transpose(0, 2, 1, 3) for x in (r, k, v, log_w))
    seq_spec = pl.BlockSpec((1, 1, chunk, N), lambda b, h, ic: (b, h, ic, 0))
    state_spec = pl.BlockSpec((1, 1, N, N), lambda b, h, ic: (b, h, 0, 0))
    y, s_fin = pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            seq_spec, seq_spec, seq_spec, seq_spec,
            pl.BlockSpec((1, 1, N), lambda b, h, ic: (h, 0, 0)),
            state_spec,
        ],
        out_specs=[seq_spec, state_spec],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S_p, N), r.dtype),
            jax.ShapeDtypeStruct((B, H, N, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, N), jnp.float32),
                        pltpu.VMEM((chunk, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(r, k, v, log_w, u[:, None, :], s0)
    y = y.transpose(0, 2, 1, 3)
    if pad:
        y = y[:, :S]
    return y, s_fin
