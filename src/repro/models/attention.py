"""Attention: GQA/MQA/MHA, causal + bidirectional + sliding-window, cross.

The reference computation is *q-chunked* (streaming) so the XLA-fused CPU/TPU
fallback path never materialises a full (Sq x Skv) score tensor — the Pallas
flash kernel (kernels/flash_attention.py) is the TPU-optimized equivalent and
is validated against this math.  Sliding-window layers additionally slice the
KV band per q-chunk, so SWA prefill is O(S * window), not O(S^2).

KV caches carry an explicit per-slot ``pos`` array (-1 = empty), which makes
full caches, ring buffers (SWA) and cross-attention caches uniform: masks are
always computed from true token positions.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import constrain, current_mesh_info
from repro.models.layers import Param, apply_rope, dense_init

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


# ---------------------------------------------------------------------------
# Context threading through the model
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ModelCtx:
    mode: str  # train | prefill | chunk_prefill | decode | encode
    positions: jax.Array  # (B, S) int32; or (3, B, S) for mrope
    cache_pos: jax.Array | None = None  # (B,) int32 write position (decode)
    enc_out: jax.Array | None = None  # (B, S_enc, d) encoder output
    enc_positions: jax.Array | None = None  # (B, S_enc)
    causal: bool = True
    #: (B, max_pages) int32 block table for paged KV pools (decode only);
    #: entries == n_pages mark unallocated logical pages.  Carried on the ctx
    #: (not in the cache pytree) so scanned segments see it as a closure
    #: capture instead of a scanned leaf.
    table: jax.Array | None = None
    #: () int32 index of the layer inside a scanned segment, set by the scan
    #: body: paged pools then arrive stacked, (layers, n_pages, ...), and are
    #: read and written at this layer in place.  None outside a scan.
    layer: jax.Array | None = None

    @property
    def pos2d(self) -> jax.Array:
        """(B, S) positions regardless of mrope (temporal component)."""
        return self.positions[0] if self.positions.ndim == 3 else self.positions


def kv_heads_shardable(n_kv_heads: int) -> bool:
    info = current_mesh_info()
    if info is None:
        return True
    return n_kv_heads % max(1, info.axis_size("model")) == 0


def cache_axes(n_kv_heads: int) -> tuple:
    """(B, S, H_kv, D) cache axes; shard heads if divisible, else the seq dim
    (SP-decode: long KV caches spread over the model axis)."""
    if kv_heads_shardable(n_kv_heads):
        return ("batch", None, "kv_heads", None)
    return ("batch", "kv_seq", None, None)


# ---------------------------------------------------------------------------
# Streaming attention core
# ---------------------------------------------------------------------------


def _pick_chunk(sq: int) -> int:
    if sq <= 1024:
        return sq
    c = max(128, min(1024, sq // 32))
    while sq % c:
        c //= 2
    return max(c, 1)


def attention_core(
    q: jax.Array,  # (B, Sq, Hq, Dk)
    k: jax.Array,  # (B, Skv, Hkv, Dk)
    v: jax.Array,  # (B, Skv, Hkv, Dv)
    pos_q: jax.Array,  # (B, Sq) int32
    pos_k: jax.Array,  # (B, Skv) int32, -1 marks empty slots
    *,
    causal: bool = True,
    window: int = 0,
    scale: float | None = None,
) -> jax.Array:
    B, Sq, Hq, Dk = q.shape
    _, Skv, Hkv, _ = k.shape
    Dv = v.shape[-1]
    G = Hq // Hkv
    scale = scale if scale is not None else Dk ** -0.5

    # Shard-aligned path for archs whose head count doesn't divide the model
    # axis (gemma 8H, minicpm3 40H on TP=16): q is *sequence*-sharded there,
    # so a q-chunk loop over the global sequence would re-gather every chunk
    # across devices each iteration (measured 9 GiB x 576 trips on the
    # baseline — EXPERIMENTS.md §Perf iteration 1).  Fold the sharded dim out
    # of the loop: reshape S -> (tp, L) keeping tp sharded, then loop over
    # L-chunks so each iteration is device-local.  Masks are computed from
    # explicit positions, so the non-contiguous row blocks stay exact.
    tp_out = _shard_aligned_attention(q.reshape(B, Sq, Hkv, G, Dk), pos_q,
                                      k, v, pos_k, causal=causal,
                                      window=window, scale=scale)
    if tp_out is not None:
        return tp_out

    # GQA: expand K/V to the q-head count instead of reshaping q into
    # (Hkv, G) groups — reshaping a TP-sharded 64-head dim into (8, 8) can't
    # stay sharded, so GSPMD replicated every attention tensor per q-chunk
    # (measured 160 GiB x 2560 trips on qwen2-vl-72b train — §Perf iteration
    # 4).  The repeat is sharding-preserving and FLOP-neutral; each device
    # ends up holding exactly the kv heads its q heads read.
    if G > 1 and Sq > 1:
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)
        if kv_heads_shardable(Hq):
            k = constrain(k, "batch", None, "heads", None)
            v = constrain(v, "batch", None, "heads", None)
        return _attention_expanded(q, k, v, pos_q, pos_k, causal=causal,
                                   window=window, scale=scale)
    if G == 1 and Sq > 1:
        return _attention_expanded(q, k, v, pos_q, pos_k, causal=causal,
                                   window=window, scale=scale)

    # decode (Sq == 1): grouped einsum against the (possibly seq-sharded)
    # cache — no repeat, so cache reads stay 1/G of the expanded cost.
    qg = q.reshape(B, Sq, Hkv, G, Dk)

    def block(q_blk: jax.Array, pq: jax.Array, k_: jax.Array, v_: jax.Array,
              pk: jax.Array) -> jax.Array:
        s = jnp.einsum("bqhgd,bkhd->bhgqk", q_blk, k_,
                       preferred_element_type=jnp.float32) * scale
        mask = (pk >= 0)[:, None, None, None, :]
        if causal:
            mask &= pk[:, None, None, None, :] <= pq[:, None, None, :, None]
        if window > 0:
            mask &= (pq[:, None, None, :, None] - pk[:, None, None, None, :]) < window
        s = jnp.where(mask, s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v_.dtype), v_)
        return o.reshape(B, -1, Hq, Dv)

    return block(qg, pos_q, k, v, pos_k)


def _attention_expanded(q, k, v, pos_q, pos_k, *, causal, window, scale):
    """Plain q-chunked attention with per-head K/V (no grouping)."""
    B, Sq, Hq, Dk = q.shape
    Skv = k.shape[1]
    Dv = v.shape[-1]

    def block(q_blk: jax.Array, pq: jax.Array, k_: jax.Array, v_: jax.Array,
              pk: jax.Array) -> jax.Array:
        s = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k_,
                       preferred_element_type=jnp.float32) * scale
        mask = (pk >= 0)[:, None, None, :]
        if causal:
            mask &= pk[:, None, None, :] <= pq[:, None, :, None]
        if window > 0:
            mask &= (pq[:, None, :, None] - pk[:, None, None, :]) < window
        s = jnp.where(mask, s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v_.dtype), v_)

    chunk = _pick_chunk(Sq)
    if Sq == chunk:
        return block(q, pos_q, k, v, pos_k)

    nc = Sq // chunk
    qc = jnp.moveaxis(q.reshape(B, nc, chunk, Hq, Dk), 1, 0)
    pc = jnp.moveaxis(pos_q.reshape(B, nc, chunk), 1, 0)

    # Banded path: for sliding-window prefill slice the KV band per q-chunk so
    # the work is O(S*window).  Valid because prefill cache slots are
    # position-ordered (pos_k == arange over the computed sequence).
    if window > 0 and Skv > (window + chunk):
        band = _round_up(window + chunk, 128)

        def banded_step(args):
            q_blk, pq, start = args
            lo = jnp.maximum(start + chunk - band, 0)
            k_b = jax.lax.dynamic_slice_in_dim(k, lo, band, axis=1)
            v_b = jax.lax.dynamic_slice_in_dim(v, lo, band, axis=1)
            pk_b = jax.lax.dynamic_slice_in_dim(pos_k, lo, band, axis=1)
            return block(q_blk, pq, k_b, v_b, pk_b)

        starts = jnp.arange(nc, dtype=jnp.int32) * chunk
        out = jax.lax.map(banded_step, (qc, pc, starts))
    else:
        out = jax.lax.map(lambda a: block(a[0], a[1], k, v, pos_k), (qc, pc))
    return jnp.moveaxis(out, 0, 1).reshape(B, Sq, Hq, Dv)


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


_SCORE_BYTES_BUDGET = 700e6  # per-device f32 score-block budget


def _attn_block_tp(q_blk, pq, k, v, pk, causal, window, scale):
    """q_blk: (B, tp, c, Hkv, G, D) with tp sharded; k/v replicated."""
    B = q_blk.shape[0]
    hq = q_blk.shape[3] * q_blk.shape[4]
    dv = v.shape[-1]
    s = jnp.einsum("btqhgd,bkhd->bhgtqk", q_blk, k,
                   preferred_element_type=jnp.float32) * scale
    mask = (pk >= 0)[:, None, None, None, None, :]
    if causal:
        mask &= pk[:, None, None, None, None, :] <= pq[:, :, :, None][:, None, None]
    if window > 0:
        mask &= (pq[:, :, :, None][:, None, None]
                 - pk[:, None, None, None, None, :]) < window
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgtqk,bkhd->btqhgd", p.astype(v.dtype), v)
    return o.reshape(B, q_blk.shape[1], q_blk.shape[2], hq, dv)


def _shard_aligned_attention(qg, pos_q, k, v, pos_k, *, causal, window,
                             scale):
    """Returns the attention output for the seq-sharded-q regime, or None if
    the plain path applies (single device / heads shardable / tiny seq)."""
    info = current_mesh_info()
    if info is None:
        return None
    tp = info.axis_size("model")
    B, Sq, Hkv, G, Dk = qg.shape
    Skv = k.shape[1]
    Hq, Dv = Hkv * G, v.shape[-1]
    if (tp <= 1 or Sq <= 1 or kv_heads_shardable(Hq) or Sq % tp
            or Sq <= _pick_chunk(Sq)):
        return None
    dp = info.axis_size("data") * info.axis_size("pod")
    b_loc = max(1, B // max(dp, 1))
    ll = Sq // tp
    row_bytes = b_loc * Hq * Skv * 4
    c2 = max(16, int(_SCORE_BYTES_BUDGET // max(row_bytes, 1)))
    c2 = min(c2, ll)
    while ll % c2:
        c2 -= 1
    qs = constrain(qg.reshape(B, tp, ll, Hkv, G, Dk),
                   "batch", "seq_act", None, None, None, None)
    ps = pos_q.reshape(B, tp, ll)
    if c2 == ll:  # one device-local block, no loop
        out = _attn_block_tp(qs, ps, k, v, pos_k, causal, window, scale)
    else:
        nc = ll // c2
        qc = jnp.moveaxis(qs.reshape(B, tp, nc, c2, Hkv, G, Dk), 2, 0)
        pc = jnp.moveaxis(ps.reshape(B, tp, nc, c2), 2, 0)
        out = jax.lax.map(
            lambda a: _attn_block_tp(a[0], a[1], k, v, pos_k, causal,
                                     window, scale), (qc, pc))
        out = jnp.moveaxis(out, 0, 2)  # (B, tp, nc*? c2, H, Dv) blocks
        out = out.reshape(B, tp, ll, Hq, Dv)
    return out.reshape(B, Sq, Hq, Dv)


# ---------------------------------------------------------------------------
# Cache plumbing (full + ring buffers, explicit slot positions)
# ---------------------------------------------------------------------------


def make_kv_cache(batch: int, size: int, n_kv: int, dk: int, dv: int, dtype) -> dict:
    return {
        "k": jnp.zeros((batch, size, n_kv, dk), dtype),
        "v": jnp.zeros((batch, size, n_kv, dv), dtype),
        "pos": jnp.full((batch, size), -1, jnp.int32),
    }


def kv_cache_specs(batch: int, size: int, n_kv: int, dk: int, dv: int, dtype) -> dict:
    ax = cache_axes(n_kv)
    return {
        "k": (jax.ShapeDtypeStruct((batch, size, n_kv, dk), dtype), ax),
        "v": (jax.ShapeDtypeStruct((batch, size, n_kv, dv), dtype), ax),
        "pos": (jax.ShapeDtypeStruct((batch, size), jnp.int32), ("batch", ax[1])),
    }


def prefill_cache(cache: dict, k: jax.Array, v: jax.Array, pos: jax.Array) -> dict:
    """Write a full prefix into a (possibly ring) cache.  For ring caches only
    the last `size` tokens are written (unique slots => deterministic)."""
    size = cache["k"].shape[1]
    S = k.shape[1]
    if S <= size:
        k_w, v_w, p_w = k, v, pos
    else:
        k_w, v_w, p_w = k[:, -size:], v[:, -size:], pos[:, -size:]
    slots = p_w % size  # unique within the window
    b_idx = jnp.arange(k.shape[0])[:, None]
    return {
        "k": cache["k"].at[b_idx, slots].set(k_w.astype(cache["k"].dtype)),
        "v": cache["v"].at[b_idx, slots].set(v_w.astype(cache["v"].dtype)),
        "pos": cache["pos"].at[b_idx, slots].set(p_w),
    }


def append_cache(cache: dict, k_t: jax.Array, v_t: jax.Array, pos: jax.Array) -> dict:
    """Append one token (decode). k_t: (B, 1, H, D); pos: (B,).

    pos < 0 marks an inactive slot (e.g. mid-chunk-prefill in the paged
    engine): its write maps to an out-of-bounds index and is dropped, so
    decoding the shared batch never clobbers a slot being prefilled."""
    size = cache["k"].shape[1]
    slots = jnp.where(pos >= 0, pos % size, size)
    b_idx = jnp.arange(k_t.shape[0])
    return {
        "k": cache["k"].at[b_idx, slots].set(k_t[:, 0].astype(cache["k"].dtype)),
        "v": cache["v"].at[b_idx, slots].set(v_t[:, 0].astype(cache["v"].dtype)),
        "pos": cache["pos"].at[b_idx, slots].set(pos),
    }


# ---------------------------------------------------------------------------
# Paged KV pools (block-table indirection, shared across decode slots)
# ---------------------------------------------------------------------------


def paged_kv_cache_specs(n_pages: int, page_size: int, n_kv: int, dk: int,
                         dv: int, dtype) -> dict:
    """Specs for a page *pool*: no batch dim — physical pages are allocated
    to slots through a block table (see launch/paged_kv.py).  The ``pages``
    logical tag is how gather/scatter code finds the pool dim.

    A token's heads are one row, ``(n_kv * d,)``.  Split as ``(n_kv, d)``
    with a head size under the TPU's 128 lanes, the pool is either padded to
    128 lanes a head or laid out pages-minor; in neither is a page one block
    of memory that decode can gather, and write a token into, in place."""
    ax = ("pages", None, "kv_heads" if kv_heads_shardable(n_kv) else None)
    return {
        "k": (jax.ShapeDtypeStruct((n_pages, page_size, n_kv * dk), dtype), ax),
        "v": (jax.ShapeDtypeStruct((n_pages, page_size, n_kv * dv), dtype), ax),
        "pos": (jax.ShapeDtypeStruct((n_pages, page_size), jnp.int32),
                ("pages", None)),
    }


@jax.named_scope("kv_pool")
def paged_append(cache: dict, k_t: jax.Array, v_t: jax.Array, pos: jax.Array,
                 table: jax.Array, layer: jax.Array | None = None) -> dict:
    """Append one token per slot into the page pool (decode).

    k_t: (B, 1, H, D); pos: (B,) absolute positions; table: (B, P).
    Slots with pos < 0 (inactive) and unallocated logical pages resolve to an
    out-of-bounds page index, so their scatter is dropped — a dead slot can
    never corrupt pages that have been recycled to another request.

    With ``layer`` the pools are a scanned segment's stacked ones,
    (layers, n_pages, ...), and the token lands at ``(layer, page, off)`` in
    place.  The page stays its own index: a flat ``layer * n_pages + page``
    would send the sentinel ``n_pages`` into the next layer's first page."""
    n_pages, ps = cache["pos"].shape[-2:]
    P = table.shape[1]
    valid = (pos >= 0) & (pos < P * ps)
    lpage = jnp.clip(pos // ps, 0, P - 1)
    page = jnp.take_along_axis(table, lpage[:, None], axis=1)[:, 0]
    page = jnp.where(valid, page, n_pages)  # OOB scatter index -> dropped
    off = pos % ps
    at = (page, off) if layer is None else (layer, page, off)

    def row(t, pool):  # (B, 1, H, D) -> (B, H * D), the pool's token row
        return t.reshape(t.shape[0], -1).astype(pool.dtype)

    return {
        "k": cache["k"].at[at].set(row(k_t, cache["k"])),
        "v": cache["v"].at[at].set(row(v_t, cache["v"])),
        "pos": cache["pos"].at[at].set(pos),
    }


# ---------------------------------------------------------------------------
# Standard (GQA) attention layer
# ---------------------------------------------------------------------------


def init_attention(key: jax.Array, cfg: ModelConfig, cross: bool = False) -> dict:
    d, h, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dt = cfg.param_dtype
    ks = jax.random.split(key, 4)
    kv_ax = "kv_heads"
    return {
        "w_q": Param(dense_init(ks[0], (d, h, hd), 1, dt), ("embed_fsdp", "heads", None)),
        "w_k": Param(dense_init(ks[1], (d, hkv, hd), 1, dt), ("embed_fsdp", kv_ax, None)),
        "w_v": Param(dense_init(ks[2], (d, hkv, hd), 1, dt), ("embed_fsdp", kv_ax, None)),
        "w_o": Param(dense_init(ks[3], (h, hd, d), 2, dt), ("heads", None, "embed_fsdp")),
    }


def apply_attention(
    p: dict,
    cfg: ModelConfig,
    x: jax.Array,  # (B, S, d)
    ctx: ModelCtx,
    cache: dict | None,
    *,
    window: int = 0,
    cross: bool = False,
    paged: bool = False,
) -> tuple[jax.Array, dict | None]:
    cdt = cfg.compute_dtype
    B, S, _ = x.shape
    heads_tp = kv_heads_shardable(cfg.n_heads)

    # Megatron-style SP->TP boundary: un-shard the sequence ONCE (bf16) so
    # the q/k/v projections and attention run TP-local.  Without this, GSPMD
    # implemented the seq->heads output resharding by gathering x in f32 per
    # projection (3x the bytes) — §Perf iteration 5.
    if heads_tp and S > 1:
        x = constrain(x, "batch", None, None)

    q = jnp.einsum("bsd,dhk->bshk", x, p["w_q"].astype(cdt))
    q = constrain(q, "batch", None if heads_tp else "seq_act",
                  "heads" if heads_tp else None, None)

    if cross:
        # K/V come from the encoder output; cached once at prefill.
        if cache is not None and ctx.mode == "decode":
            k, v, pos_k = cache["k"], cache["v"], cache["pos"]
            new_cache = cache
        else:
            src = ctx.enc_out
            k = jnp.einsum("bsd,dhk->bshk", src, p["w_k"].astype(cdt))
            v = jnp.einsum("bsd,dhk->bshk", src, p["w_v"].astype(cdt))
            pos_k = ctx.enc_positions
            new_cache = None
            if cache is not None:  # prefill: persist cross K/V
                new_cache = prefill_cache(cache, k, v, pos_k)
        pos_q = ctx.pos2d
        o = attention_core(q, k.astype(cdt), v.astype(cdt), pos_q, pos_k,
                           causal=False, window=0)
    else:
        k = jnp.einsum("bsd,dhk->bshk", x, p["w_k"].astype(cdt))
        v = jnp.einsum("bsd,dhk->bshk", x, p["w_v"].astype(cdt))
        if cfg.pos_type in ("rope", "mrope"):
            q = apply_rope(q, ctx.positions, cfg)
            k = apply_rope(k, ctx.positions, cfg)
        pos_q = ctx.pos2d
        kv_ax = cache_axes(cfg.n_kv_heads)
        new_cache = None
        if cache is None:  # train / encode: attend within the computed seq
            k_att, v_att, pos_k = k, v, pos_q
            o = attention_core(q, k_att, v_att, pos_q, pos_k,
                               causal=ctx.causal, window=window)
        elif ctx.mode == "decode" and paged:
            # Page-pool cache: scatter the new token through the block table,
            # then attend over the slot's gathered pages (kernels/ops).
            new_cache = paged_append(cache, k, v, ctx.cache_pos, ctx.table,
                                     layer=ctx.layer)
            from repro.kernels import ops as kops
            o = kops.paged_attention(
                q, new_cache["k"], new_cache["v"], new_cache["pos"],
                ctx.table, pos_q, causal=ctx.causal, window=window,
                layer=ctx.layer, dtype=cdt)
        elif ctx.mode == "decode":
            new_cache = append_cache(cache, k, v, ctx.cache_pos)
            k_att = constrain(new_cache["k"], *kv_ax).astype(cdt)
            v_att = constrain(new_cache["v"], *kv_ax).astype(cdt)
            pos_k = new_cache["pos"]
            o = attention_core(q, k_att, v_att, pos_q, pos_k,
                               causal=ctx.causal, window=window)
        elif ctx.mode == "chunk_prefill":
            # Continue a prefix already in the cache: attend over (cache
            # contents ∪ this chunk), then persist the chunk.  Works for full
            # caches and SWA rings alike — masks come from explicit positions,
            # and empty slots carry pos == -1.
            k_att = jnp.concatenate([cache["k"].astype(cdt), k], axis=1)
            v_att = jnp.concatenate([cache["v"].astype(cdt), v], axis=1)
            pos_k = jnp.concatenate([cache["pos"], pos_q], axis=1)
            new_cache = prefill_cache(cache, k, v, pos_q)
            o = attention_core(q, k_att, v_att, pos_q, pos_k,
                               causal=ctx.causal, window=window)
        else:  # prefill: attend over computed seq, persist into cache
            new_cache = prefill_cache(cache, k, v, pos_q)
            k_att, v_att, pos_k = k, v, pos_q
            o = attention_core(q, k_att, v_att, pos_q, pos_k,
                               causal=ctx.causal, window=window)

    o = constrain(o, "batch", None if heads_tp else "seq_act",
                  "heads" if heads_tp else None, None)
    out = jnp.einsum("bshk,hkd->bsd", o, p["w_o"].astype(cdt))
    return constrain(out, "batch", "seq_act", None), new_cache
