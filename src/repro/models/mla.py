"""Multi-head Latent Attention (DeepSeek-V2 [arXiv:2405.04434], MiniCPM3).

Train/prefill use the expanded path (latent -> per-head K/V).  Decode uses the
*weight-absorbed* path: scores and attention outputs are computed directly in
the compressed latent space, so the KV cache holds only
(kv_lora_rank + qk_rope_head_dim) floats per token — the paper's 93.3% cache
reduction — and per-step FLOPs stay O(S * kv_lora) instead of O(S * H * dh).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import constrain
from repro.models.attention import ModelCtx, attention_core, kv_heads_shardable
from repro.models.layers import (Param, apply_norm, apply_rope, dense_init,
                                 yarn_mscale)


def init_mla(key: jax.Array, cfg: ModelConfig) -> dict:
    d, h = cfg.d_model, cfg.n_heads
    nope, rope, vdim = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    qk = nope + rope
    dt = cfg.param_dtype
    ks = jax.random.split(key, 8)
    p: dict = {}
    if cfg.q_lora_rank:
        p["w_dq"] = Param(dense_init(ks[0], (d, cfg.q_lora_rank), 1, dt),
                          ("embed_fsdp", "lora"))
        p["q_norm"] = {"scale": Param(jnp.ones((cfg.q_lora_rank,), dt), (None,))}
        p["w_uq"] = Param(dense_init(ks[1], (cfg.q_lora_rank, h, qk), 1, dt),
                          ("lora", "heads", None))
    else:
        p["w_uq"] = Param(dense_init(ks[1], (d, h, qk), 1, dt),
                          ("embed_fsdp", "heads", None))
    p["w_dkv"] = Param(dense_init(ks[2], (d, cfg.kv_lora_rank), 1, dt),
                       ("embed_fsdp", "lora"))
    p["kv_norm"] = {"scale": Param(jnp.ones((cfg.kv_lora_rank,), dt), (None,))}
    p["w_kr"] = Param(dense_init(ks[3], (d, rope), 1, dt), ("embed_fsdp", None))
    p["w_uk"] = Param(dense_init(ks[4], (cfg.kv_lora_rank, h, nope), 1, dt),
                      ("lora", "heads", None))
    p["w_uv"] = Param(dense_init(ks[5], (cfg.kv_lora_rank, h, vdim), 1, dt),
                      ("lora", "heads", None))
    p["w_o"] = Param(dense_init(ks[6], (h, vdim, d), 2, dt),
                     ("heads", None, "embed_fsdp"))
    return p


def softmax_scale(cfg: ModelConfig) -> float:
    """(nope + rope)^-0.5, times mscale(factor, mscale_all_dim)^2 under
    YaRN (DeepSeek-V2: 1.58963 x the plain scale)."""
    m = yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim)
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5 * m * m


def _rms(p_scale: jax.Array, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    sub = {"scale": p_scale}
    fake = cfg.scaled(norm_type="rmsnorm", gemma_norm=False)
    return apply_norm(sub, fake, x)


def _queries(p: dict, cfg: ModelConfig, x: jax.Array, ctx: ModelCtx):
    """MLA's low-rank structure doubles as a communication compressor: the
    down-projection runs *sequence-sharded* (local), and only the q_lora_rank
    latent crosses the SP->TP boundary — 1536 of 5120 dims on deepseek-v2
    (§Perf iteration 6)."""
    cdt = cfg.compute_dtype
    nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    heads_tp = kv_heads_shardable(cfg.n_heads)
    gather = heads_tp and x.shape[1] > 1
    if cfg.q_lora_rank:
        cq = x @ p["w_dq"].astype(cdt)
        cq = _rms(p["q_norm"]["scale"], cfg, cq)
        if gather:
            cq = constrain(cq, "batch", None, None)  # SP->TP on the latent
        q = jnp.einsum("bsl,lhk->bshk", cq, p["w_uq"].astype(cdt))
    else:
        if gather:
            x = constrain(x, "batch", None, None)
        q = jnp.einsum("bsd,dhk->bshk", x, p["w_uq"].astype(cdt))
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = apply_rope(q_rope, ctx.positions, cfg, rot_dim=rope)
    return q_nope, q_rope


def _latents(p: dict, cfg: ModelConfig, x: jax.Array, ctx: ModelCtx):
    """Compressed per-token cache content: normed c_kv + roped shared k_rope."""
    cdt = cfg.compute_dtype
    ckv = x @ p["w_dkv"].astype(cdt)
    ckv = _rms(p["kv_norm"]["scale"], cfg, ckv)
    kr = (x @ p["w_kr"].astype(cdt))[:, :, None, :]  # (B,S,1,rope)
    kr = apply_rope(kr, ctx.positions, cfg, rot_dim=cfg.qk_rope_head_dim)[:, :, 0]
    return ckv, kr


def make_mla_cache(batch: int, size: int, cfg: ModelConfig, dtype) -> dict:
    return {
        "ckv": jnp.zeros((batch, size, cfg.kv_lora_rank), dtype),
        "kr": jnp.zeros((batch, size, cfg.qk_rope_head_dim), dtype),
        "pos": jnp.full((batch, size), -1, jnp.int32),
    }


def mla_cache_specs(batch: int, size: int, cfg: ModelConfig, dtype) -> dict:
    ax = ("batch", "kv_seq", None)
    return {
        "ckv": (jax.ShapeDtypeStruct((batch, size, cfg.kv_lora_rank), dtype), ax),
        "kr": (jax.ShapeDtypeStruct((batch, size, cfg.qk_rope_head_dim), dtype), ax),
        "pos": (jax.ShapeDtypeStruct((batch, size), jnp.int32), ("batch", "kv_seq")),
    }


def apply_mla(
    p: dict,
    cfg: ModelConfig,
    x: jax.Array,
    ctx: ModelCtx,
    cache: dict | None,
) -> tuple[jax.Array, dict | None]:
    cdt = cfg.compute_dtype
    B, S, _ = x.shape
    h = cfg.n_heads
    nope, rope, vdim = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    heads_tp = kv_heads_shardable(h)

    q_nope, q_rope = _queries(p, cfg, x, ctx)

    if ctx.mode in ("decode", "chunk_prefill"):
        assert cache is not None
        # ---- absorbed path (latent-space attention) ------------------------
        # decode: one new token per slot; chunk_prefill: a chunk of C tokens
        # continuing a prefix already in the cache.  Both write their latents
        # into the cache and attend with a (B, Q, S) position mask, so the
        # multi-query case is the exact generalization of single-token decode.
        ckv_t, kr_t = _latents(p, cfg, x, ctx)
        size = cache["ckv"].shape[1]
        if ctx.mode == "decode":
            b_idx = jnp.arange(B)
            # pos < 0 = inactive slot: write lands out of bounds -> dropped
            slots = jnp.where(ctx.cache_pos >= 0, ctx.cache_pos % size, size)
            new_cache = {
                "ckv": cache["ckv"].at[b_idx, slots].set(ckv_t[:, 0].astype(cache["ckv"].dtype)),
                "kr": cache["kr"].at[b_idx, slots].set(kr_t[:, 0].astype(cache["kr"].dtype)),
                "pos": cache["pos"].at[b_idx, slots].set(ctx.cache_pos),
            }
            ckv = constrain(new_cache["ckv"], "batch", "kv_seq", None).astype(cdt)
            kr = constrain(new_cache["kr"], "batch", "kv_seq", None).astype(cdt)
            pos_k = new_cache["pos"]
            pos_q = ctx.cache_pos[:, None]  # (B, 1)
        else:
            pos_q = ctx.pos2d  # (B, C)
            slots = pos_q % size
            b_idx = jnp.arange(B)[:, None]
            new_cache = {
                "ckv": cache["ckv"].at[b_idx, slots].set(ckv_t.astype(cache["ckv"].dtype)),
                "kr": cache["kr"].at[b_idx, slots].set(kr_t.astype(cache["kr"].dtype)),
                "pos": cache["pos"].at[b_idx, slots].set(pos_q),
            }
            # attend over (old cache contents ∪ this chunk); empty cache slots
            # carry pos == -1 and drop out of the mask
            ckv = jnp.concatenate([cache["ckv"].astype(cdt), ckv_t], axis=1)
            kr = jnp.concatenate([cache["kr"].astype(cdt), kr_t], axis=1)
            pos_k = jnp.concatenate([cache["pos"], pos_q], axis=1)

        # absorb W_uk into q: (B,Q,H,nope) x (lora,H,nope) -> (B,Q,H,lora)
        q_lat = jnp.einsum("bqhn,lhn->bqhl", q_nope, p["w_uk"].astype(cdt))
        s = jnp.einsum("bqhl,bsl->bhqs", q_lat, ckv,
                       preferred_element_type=jnp.float32)
        s += jnp.einsum("bqhr,bsr->bhqs", q_rope, kr,
                        preferred_element_type=jnp.float32)
        s *= softmax_scale(cfg)
        mask = (pos_k[:, None, :] >= 0) & (pos_k[:, None, :] <= pos_q[:, :, None])
        s = jnp.where(mask[:, None], s, -0.7 * jnp.finfo(jnp.float32).max)
        w = jax.nn.softmax(s, axis=-1)
        o_lat = jnp.einsum("bhqs,bsl->bqhl", w.astype(cdt), ckv)
        o = jnp.einsum("bqhl,lhv->bqhv", o_lat, p["w_uv"].astype(cdt))
    else:
        # ---- expanded train/prefill path -----------------------------------
        # latents computed sequence-sharded; only (kv_lora + rope) dims cross
        # the SP->TP boundary (512+64 of 5120 on deepseek-v2)
        ckv, kr = _latents(p, cfg, x, ctx)
        head_ax = "heads" if heads_tp else None
        seq_ax = None if heads_tp else "seq_act"
        if heads_tp and S > 1:
            ckv = constrain(ckv, "batch", None, None)
            kr = constrain(kr, "batch", None, None)
        k_nope = jnp.einsum("bsl,lhn->bshn", ckv, p["w_uk"].astype(cdt))
        k_nope = constrain(k_nope, "batch", seq_ax, head_ax, None)
        v = jnp.einsum("bsl,lhv->bshv", ckv, p["w_uv"].astype(cdt))
        v = constrain(v, "batch", seq_ax, head_ax, None)
        # pin the head-broadcast rope key + the concat so GSPMD keeps the TP
        # head sharding through them (a broadcast+concat otherwise replicated
        # all 128 heads per q-chunk on deepseek-v2 — §Perf iteration 4)
        kr_b = constrain(jnp.broadcast_to(kr[:, :, None, :], (B, S, h, rope)),
                         "batch", seq_ax, head_ax, None)
        k = constrain(jnp.concatenate([k_nope, kr_b], axis=-1),
                      "batch", seq_ax, head_ax, None)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        q = constrain(q, "batch", seq_ax, head_ax, None)
        pos = ctx.pos2d
        o = attention_core(q, k, v, pos, pos, causal=ctx.causal,
                           scale=softmax_scale(cfg))
        new_cache = None
        if cache is not None:  # prefill: persist compressed latents
            size = cache["ckv"].shape[1]
            ckv_w = ckv[:, -size:] if S > size else ckv
            kr_w = kr[:, -size:] if S > size else kr
            p_w = pos[:, -size:] if S > size else pos
            slots = p_w % size
            b_idx = jnp.arange(B)[:, None]
            new_cache = {
                "ckv": cache["ckv"].at[b_idx, slots].set(ckv_w.astype(cache["ckv"].dtype)),
                "kr": cache["kr"].at[b_idx, slots].set(kr_w.astype(cache["kr"].dtype)),
                "pos": cache["pos"].at[b_idx, slots].set(p_w),
            }

    o = constrain(o, "batch", None if heads_tp else "seq_act",
                  "heads" if heads_tp else None, None)
    out = jnp.einsum("bshv,hvd->bsd", o, p["w_o"].astype(cdt))
    return constrain(out, "batch", "seq_act", None), new_cache
