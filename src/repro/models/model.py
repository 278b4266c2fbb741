"""LanguageModel: unified init / train_loss / prefill / decode_step for all
ten assigned architectures (dense, MoE, MLA, hybrid, SSM, enc-dec, VLM).

Pure-functional: ``init`` returns a plain array pytree; the logical sharding
axes for every parameter are captured as a parallel tree (``param_axes``).
The same apply code runs un-sharded in unit tests and under GSPMD on the
production meshes (sharding constraints no-op without an active mesh).
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import constrain
from repro.models import moe as moe_mod
from repro.models import transformer as tfm
from repro.models.attention import ModelCtx
from repro.models.layers import (Param, apply_norm, embed_init, init_norm,
                                 sinusoidal_positions, split)


class LanguageModel:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.dec_kinds = tfm.layer_kinds(cfg, decoder=cfg.enc_dec)
        self.dec_segments = tfm.plan_segments(cfg, self.dec_kinds)
        self.enc_segments = []
        if cfg.enc_dec:
            enc_kinds = [("attn", False)] * cfg.n_enc_layers
            self.enc_segments = tfm.plan_segments(cfg, enc_kinds)
        self._axes: dict | None = None

    # ------------------------------------------------------------------ init
    def init(self, key: jax.Array) -> dict:
        cfg = self.cfg
        axes: dict[str, Any] = {}
        params: dict[str, Any] = {}
        n_keys = 8 + len(self.dec_segments) + len(self.enc_segments)
        ks = list(jax.random.split(key, n_keys))

        def take(p: Param):
            return p.value, tuple(p.axes)

        params["embed"], axes["embed"] = take(Param(
            embed_init(ks.pop(), (cfg.vocab_size, cfg.d_model), cfg.param_dtype),
            ("vocab", "embed_fsdp")))
        if not cfg.tie_embeddings:
            params["out"], axes["out"] = take(Param(
                embed_init(ks.pop(), (cfg.d_model, cfg.vocab_size), cfg.param_dtype),
                ("embed_fsdp", "vocab")))
        if cfg.pos_type == "learned":
            params["pos_embed"], axes["pos_embed"] = take(Param(
                embed_init(ks.pop(), (cfg.max_positions, cfg.d_model),
                           cfg.param_dtype),
                (None, "embed_fsdp")))
        if cfg.embed_norm:
            v, a = split(init_norm(cfg, cfg.d_model))
            params["embed_ln"], axes["embed_ln"] = v, a

        for i, seg in enumerate(self.dec_segments):
            cap: dict = {}
            params[f"seg{i}"] = tfm.init_segment(ks.pop(), cfg, seg, cap)
            axes[f"seg{i}"] = cap["axes"]
        v, a = split(init_norm(cfg, cfg.d_model))
        params["final_norm"], axes["final_norm"] = v, a

        if cfg.enc_dec:
            enc_p: dict = {}
            enc_a: dict = {}
            for i, seg in enumerate(self.enc_segments):
                cap = {}
                enc_p[f"seg{i}"] = tfm.init_segment(ks.pop(), cfg, seg, cap)
                enc_a[f"seg{i}"] = cap["axes"]
            v, a = split(init_norm(cfg, cfg.d_model))
            enc_p["final_norm"], enc_a["final_norm"] = v, a
            params["enc"], axes["enc"] = enc_p, enc_a

        self._axes = axes
        return params

    @property
    def param_axes(self) -> dict:
        if self._axes is None:
            jax.eval_shape(self.init, jax.random.PRNGKey(0))
        return self._axes  # type: ignore[return-value]

    def abstract_params(self) -> dict:
        return jax.eval_shape(self.init, jax.random.PRNGKey(0))

    # ------------------------------------------------------------- embeddings
    @jax.named_scope("embed")
    def _embed(self, params: dict, tokens: jax.Array,
               embeds: jax.Array | None = None) -> jax.Array:
        cfg = self.cfg
        cdt = cfg.compute_dtype
        if embeds is not None:
            x = embeds.astype(cdt)  # modality-frontend stub output
        else:
            x = jnp.take(params["embed"], tokens, axis=0).astype(cdt)
        if cfg.emb_scale:
            x = x * math.sqrt(cfg.d_model)
        if cfg.embed_norm:
            x = apply_norm(params["embed_ln"], cfg, x)
        return constrain(x, "batch", "seq_act", None)

    @jax.named_scope("lm_head")
    def _head(self, params: dict, x: jax.Array) -> jax.Array:
        cfg = self.cfg
        x = apply_norm(params["final_norm"], cfg, x)
        w = params["embed"].T if cfg.tie_embeddings else params["out"]
        logits = (x @ w.astype(cfg.compute_dtype)).astype(jnp.float32)
        return constrain(logits, "batch", "seq_act", "vocab")

    def _positions(self, batch_size: int, seq: int,
                   given: jax.Array | None) -> jax.Array:
        if given is not None:
            return given
        pos = jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32), (batch_size, seq))
        if self.cfg.pos_type == "mrope":
            pos = jnp.broadcast_to(pos, (3, batch_size, seq))
        return pos

    # --------------------------------------------------------------- encoder
    def _encode(self, params: dict, frames: jax.Array) -> tuple[jax.Array, jax.Array]:
        cfg = self.cfg
        B, S, _ = frames.shape
        x = frames.astype(cfg.compute_dtype)
        x = x + sinusoidal_positions(S, cfg.d_model, x.dtype)[None]
        x = constrain(x, "batch", "seq_act", None)
        pos = self._positions(B, S, None)
        ctx = ModelCtx(mode="encode", positions=pos, causal=False)
        enc_axes = self.param_axes.get("enc", {})
        for i, seg in enumerate(self.enc_segments):
            x, _, _ = tfm.apply_segment(params["enc"][f"seg{i}"], cfg, seg, x,
                                        None, ctx, axes=enc_axes.get(f"seg{i}"))
        x = apply_norm(params["enc"]["final_norm"], cfg, x)
        return x, pos

    def _cast_for_compute(self, params: dict) -> dict:
        """Cast >=2D float params to the compute dtype *before* use: the cast
        runs on local FSDP shards, so per-layer all-gathers move bf16, not
        f32 (halves FSDP gather traffic — EXPERIMENTS.md §Perf)."""
        cdt = self.cfg.compute_dtype
        if jnp.dtype(cdt) == jnp.dtype(self.cfg.param_dtype):
            return params

        def cast(x):
            if (hasattr(x, "dtype") and x.ndim >= 2
                    and jnp.issubdtype(x.dtype, jnp.floating)):
                return x.astype(cdt)
            return x

        return jax.tree.map(cast, params)

    def _backbone(self, params: dict, x: jax.Array, caches: Any,
                  ctx: ModelCtx) -> tuple[jax.Array, Any, dict]:
        new_caches = {} if caches is not None else None
        aux = moe_mod.zero_stats()
        axes = self.param_axes
        for i, seg in enumerate(self.dec_segments):
            c = None if caches is None else caches[f"seg{i}"]
            x, nc, a = tfm.apply_segment(params[f"seg{i}"], self.cfg, seg, x,
                                         c, ctx, axes=axes.get(f"seg{i}"))
            aux = jax.tree.map(jnp.add, aux, a)
            if new_caches is not None:
                new_caches[f"seg{i}"] = nc
        return x, new_caches, aux

    # ------------------------------------------------------------------ train
    def train_loss(self, params: dict, batch: dict) -> tuple[jax.Array, dict]:
        cfg = self.cfg
        tokens = batch["tokens"]
        B, S = tokens.shape
        targets = batch["targets"]
        weights = batch.get("weights")
        if weights is None:
            weights = jnp.ones_like(tokens, jnp.float32)
        params = self._cast_for_compute(params)
        pos = self._positions(B, S, batch.get("positions"))
        ctx = ModelCtx(mode="train", positions=pos)
        if cfg.enc_dec:
            enc_out, enc_pos = self._encode(params, batch["frames"])
            ctx = ModelCtx(mode="train", positions=pos, enc_out=enc_out,
                           enc_positions=enc_pos)

        x = self._embed(params, tokens, batch.get("embeds"))
        if cfg.pos_type == "learned":
            x = x + jnp.take(params["pos_embed"], pos, axis=0).astype(x.dtype)
        x, _, aux = self._backbone(params, x, None, ctx)
        logits = self._head(params, x)

        lse = jax.nn.logsumexp(logits, axis=-1)
        onehot = jax.nn.one_hot(targets, cfg.vocab_size, dtype=logits.dtype)
        label_logit = jnp.sum(onehot * logits, axis=-1)
        nll = (lse - label_logit) * weights
        denom = jnp.maximum(weights.sum(), 1.0)
        loss = nll.sum() / denom
        total = loss + cfg.router_aux_coef * aux["load_balance"]
        metrics = {"loss": loss, "aux_loss": aux["load_balance"],
                   "moe_dropped": aux["dropped"], "tokens": denom,
                   "total_loss": total}
        return total, metrics

    # ------------------------------------------------------------------ serve
    def cache_specs(self, batch: int, max_len: int, enc_len: int = 0,
                    dtype=jnp.bfloat16,
                    pages: tuple[int, int] | None = None) -> dict:
        """``pages=(n_pages, page_size)`` swaps full-attention KV caches for
        shared page pools (no batch dim; see launch/paged_kv.py).  All other
        cache kinds (SWA rings, cross, MLA latents, recurrent states) remain
        slot-dense with ``batch`` rows."""
        specs = {}
        for i, seg in enumerate(self.dec_segments):
            specs[f"seg{i}"] = tfm.segment_cache_specs(
                self.cfg, seg, batch, max_len, enc_len or max_len, dtype,
                pages=pages)
        return specs

    def init_cache(self, batch: int, max_len: int, enc_len: int = 0,
                   dtype=jnp.bfloat16,
                   pages: tuple[int, int] | None = None) -> dict:
        def make(leaf):
            sds, _ = leaf
            if sds.dtype == jnp.int32:  # slot-position arrays start empty
                return jnp.full(sds.shape, -1, sds.dtype)
            return jnp.zeros(sds.shape, sds.dtype)

        return jax.tree.map(
            make, self.cache_specs(batch, max_len, enc_len, dtype,
                                   pages=pages),
            is_leaf=_is_spec_leaf)

    def prefill(self, params: dict, batch: dict, cache: dict) -> tuple[jax.Array, dict]:
        cfg = self.cfg
        tokens = batch["tokens"]
        B, S = tokens.shape
        pos = self._positions(B, S, batch.get("positions"))
        ctx = ModelCtx(mode="prefill", positions=pos)
        if cfg.enc_dec:
            enc_out, enc_pos = self._encode(params, batch["frames"])
            ctx = ModelCtx(mode="prefill", positions=pos, enc_out=enc_out,
                           enc_positions=enc_pos)
        x = self._embed(params, tokens, batch.get("embeds"))
        if cfg.pos_type == "learned":
            x = x + jnp.take(params["pos_embed"], pos, axis=0).astype(x.dtype)
        x, new_cache, _ = self._backbone(params, x, cache, ctx)
        logits = self._head(params, x[:, -1:])[:, 0]
        return logits, new_cache

    def prefill_chunk(self, params: dict, batch: dict, cache: dict,
                      start: jax.Array) -> tuple[jax.Array, dict]:
        """Continue prefilling an existing cache with one chunk of tokens.

        batch["tokens"]: (B, C); start: (B,) absolute position of the chunk's
        first token.  Attends over (cache contents ∪ chunk), so calling this
        repeatedly over an exact partition of the prompt is equivalent to one
        full ``prefill`` — no padding, no masking approximations.  Returns the
        last-position logits (the argmax seed once the prompt is exhausted)
        and the updated cache.
        """
        cfg = self.cfg
        tokens = batch["tokens"]
        B, C = tokens.shape
        pos = start[:, None].astype(jnp.int32) + jnp.arange(C, dtype=jnp.int32)
        if cfg.pos_type == "mrope":
            pos = jnp.broadcast_to(pos, (3, B, C))
        ctx = ModelCtx(mode="chunk_prefill", positions=pos)
        if cfg.enc_dec:
            enc_out, enc_pos = self._encode(params, batch["frames"])
            ctx = ModelCtx(mode="chunk_prefill", positions=pos,
                           enc_out=enc_out, enc_positions=enc_pos)
        x = self._embed(params, tokens, batch.get("embeds"))
        if cfg.pos_type == "learned":
            x = x + jnp.take(params["pos_embed"], pos, axis=0).astype(x.dtype)
        x, new_cache, _ = self._backbone(params, x, cache, ctx)
        logits = self._head(params, x[:, -1:])[:, 0]
        return logits, new_cache

    def decode_step(self, params: dict, tokens: jax.Array, cache: dict,
                    pos: jax.Array,
                    table: jax.Array | None = None) -> tuple[jax.Array, dict]:
        """tokens: (B, 1); pos: (B,) current positions (0-based).  ``table``
        is the (B, max_pages) block table when ``cache`` holds paged pools."""
        cfg = self.cfg
        B = tokens.shape[0]
        positions = pos[:, None].astype(jnp.int32)
        if cfg.pos_type == "mrope":
            positions = jnp.broadcast_to(positions, (3, B, 1))
        ctx = ModelCtx(mode="decode", positions=positions, cache_pos=pos,
                       table=table)
        x = self._embed(params, tokens)
        if cfg.pos_type == "learned":
            x = x + jnp.take(params["pos_embed"], positions, axis=0).astype(x.dtype)
        x, new_cache, _ = self._backbone(params, x, cache, ctx)
        logits = self._head(params, x)[:, 0]
        return logits, new_cache


def _is_spec_leaf(x: Any) -> bool:
    return (isinstance(x, tuple) and len(x) == 2
            and isinstance(x[0], jax.ShapeDtypeStruct))
