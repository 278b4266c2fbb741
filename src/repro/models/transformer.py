"""Block composition: per-layer kinds -> scanned segments.

Layers are grouped into *segments*: a maximal run of layers whose cyclic
super-block (e.g. Griffin's (rglru, rglru, swa)) repeats >= 2 times is scanned
with ``jax.lax.scan`` (keeping HLO compact and making FSDP all-gathers land
inside the loop body); leftovers are unrolled.  Examples:

  deepseek-v2-236b : [dense x1 unrolled] + [moe x59 scanned]
  recurrentgemma-9b: [(rglru,rglru,swa) x12 scanned] + [rglru, rglru unrolled]
  gemma-2b         : [(attn) x18 scanned]
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn_mod
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models import recurrent as rec_mod
from repro.models.attention import ModelCtx
from repro.models.layers import apply_mlp, apply_norm, init_mlp, init_norm, split

LayerKind = tuple[str, bool]  # (block type, is_moe)


@dataclasses.dataclass(frozen=True)
class Segment:
    kinds: tuple[LayerKind, ...]  # the super-block
    repeats: int
    scanned: bool

    @property
    def n_layers(self) -> int:
        return len(self.kinds) * self.repeats


def layer_kinds(cfg: ModelConfig, decoder: bool = False) -> list[LayerKind]:
    if decoder:
        return [("xattn", False)] * cfg.n_layers
    kinds = []
    for i, t in enumerate(cfg.layer_types()):
        moe = (cfg.n_experts > 0 and i >= cfg.first_dense_layers
               and t in ("attn", "swa"))
        kinds.append((t, moe))
    return kinds


def plan_segments(cfg: ModelConfig, kinds: list[LayerKind]) -> list[Segment]:
    p = max(1, len(cfg.layer_pattern))
    segs: list[Segment] = []
    i, n = 0, len(kinds)
    while i < n:
        block = tuple(kinds[i : i + p])
        reps = 0
        j = i
        while j + p <= n and tuple(kinds[j : j + p]) == block:
            reps += 1
            j += p
        if reps >= 2:
            segs.append(Segment(block, reps, scanned=True))
            i = j
        else:
            segs.append(Segment((kinds[i],), 1, scanned=False))
            i += 1
    return segs


# ---------------------------------------------------------------------------
# Single layer
# ---------------------------------------------------------------------------


def init_layer(key: jax.Array, cfg: ModelConfig, kind: LayerKind) -> dict:
    t, is_moe = kind
    ks = jax.random.split(key, 6)
    p: dict = {"norm1": init_norm(cfg, cfg.d_model)}
    if t in ("attn", "swa"):
        p["core"] = attn_mod.init_attention(ks[0], cfg)
        if cfg.use_mla:
            p["core"] = mla_mod.init_mla(ks[0], cfg)
    elif t == "xattn":
        p["core"] = attn_mod.init_attention(ks[0], cfg)
        p["norm_x"] = init_norm(cfg, cfg.d_model)
        p["cross"] = attn_mod.init_attention(ks[1], cfg, cross=True)
    elif t == "rglru":
        p["core"] = rec_mod.init_rglru(ks[0], cfg)
    elif t == "rwkv6":
        p["core"] = rec_mod.init_rwkv_time_mix(ks[0], cfg)
    else:
        raise ValueError(t)

    p["norm2"] = init_norm(cfg, cfg.d_model)
    if t == "rwkv6":
        p["mlp"] = rec_mod.init_rwkv_channel_mix(ks[2], cfg)
    elif is_moe:
        p["moe"] = moe_mod.init_moe(ks[2], cfg)
        if cfg.n_shared_experts:
            p["shared"] = init_mlp(ks[3], cfg,
                                   cfg.n_shared_experts * cfg.d_ff_expert)
    else:
        p["mlp"] = init_mlp(ks[2], cfg)
    return p


def cache_specs_for_kind(cfg: ModelConfig, kind: LayerKind, batch: int,
                         max_len: int, enc_len: int, dtype,
                         pages: tuple[int, int] | None = None) -> Any:
    """``pages=(n_pages, page_size)`` swaps full-attention KV caches for
    shared page pools (block-table indirection; see launch/paged_kv.py).
    SWA rings, cross caches, MLA latents and recurrent states stay slot-dense
    — they are O(window)/O(1) per slot, so paging buys nothing there."""
    t, _ = kind
    if t == "swa":
        size = min(cfg.window, max_len) if cfg.window else max_len
        return attn_mod.kv_cache_specs(batch, size, cfg.n_kv_heads,
                                       cfg.head_dim, cfg.head_dim, dtype)
    if t == "attn":
        if cfg.use_mla:
            return mla_mod.mla_cache_specs(batch, max_len, cfg, dtype)
        if pages is not None:
            return attn_mod.paged_kv_cache_specs(
                pages[0], pages[1], cfg.n_kv_heads, cfg.head_dim,
                cfg.head_dim, dtype)
        return attn_mod.kv_cache_specs(batch, max_len, cfg.n_kv_heads,
                                       cfg.head_dim, cfg.head_dim, dtype)
    if t == "xattn":
        return {
            "self": attn_mod.kv_cache_specs(batch, max_len, cfg.n_kv_heads,
                                            cfg.head_dim, cfg.head_dim, dtype),
            "cross": attn_mod.kv_cache_specs(batch, enc_len, cfg.n_kv_heads,
                                             cfg.head_dim, cfg.head_dim, dtype),
        }
    if t == "rglru":
        return rec_mod.rglru_state_specs(batch, cfg)
    if t == "rwkv6":
        return rec_mod.rwkv_state_specs(batch, cfg)
    raise ValueError(t)


def _active_mask(ctx: ModelCtx) -> jax.Array | None:
    """Per-slot liveness for decode: pos < 0 marks a slot whose recurrent
    state must pass through unchanged (it is being chunk-prefilled while the
    rest of the batch decodes)."""
    if ctx.mode == "decode" and ctx.cache_pos is not None:
        return ctx.cache_pos >= 0
    return None


def _mixer_scope(cfg: ModelConfig, t: str) -> str:
    """The named scope of a layer's token mixer (profiles and op metadata)."""
    if t in ("rglru", "rwkv6"):
        return "recurrent"
    return "mla" if cfg.use_mla and t in ("attn", "swa") else "attn"


def _paged(cfg: ModelConfig, kind: LayerKind, ctx: ModelCtx) -> bool:
    """Whether a sub-layer decodes through a page pool.  Only full-attention
    layers page; the rule (not cache structure sniffing) decides, because
    inside a scanned segment the cache is a tracer whose paged-ness can't be
    inspected."""
    return (kind[0] == "attn" and not cfg.use_mla and ctx.mode == "decode"
            and ctx.table is not None)


def apply_layer(p: dict, cfg: ModelConfig, kind: LayerKind, x: jax.Array,
                cache: Any, ctx: ModelCtx) -> tuple[jax.Array, Any, dict]:
    t, is_moe = kind
    aux = moe_mod.zero_stats()
    h = apply_norm(p["norm1"], cfg, x)

    with jax.named_scope(_mixer_scope(cfg, t)):
        if t in ("attn", "swa"):
            window = cfg.window if t == "swa" else 0
            if cfg.use_mla:
                y, new_cache = mla_mod.apply_mla(p["core"], cfg, h, ctx,
                                                 cache)
            else:
                y, new_cache = attn_mod.apply_attention(
                    p["core"], cfg, h, ctx, cache, window=window,
                    paged=_paged(cfg, kind, ctx))
        elif t == "xattn":
            y, self_c = attn_mod.apply_attention(
                p["core"], cfg, h, ctx,
                None if cache is None else cache["self"])
            x = x + y
            hx = apply_norm(p["norm_x"], cfg, x)
            y, cross_c = attn_mod.apply_attention(
                p["cross"], cfg, hx, ctx,
                None if cache is None else cache["cross"], cross=True)
            new_cache = (None if cache is None
                         else {"self": self_c, "cross": cross_c})
        elif t == "rglru":
            y, new_cache = rec_mod.apply_rglru(p["core"], cfg, h, cache,
                                               ctx.mode,
                                               active=_active_mask(ctx))
        elif t == "rwkv6":
            y, new_cache = rec_mod.apply_rwkv_time_mix(
                p["core"], cfg, h, cache, ctx.mode, active=_active_mask(ctx))
        else:
            raise ValueError(t)
    x = x + y

    h = apply_norm(p["norm2"], cfg, x)
    with jax.named_scope("moe" if is_moe and t != "rwkv6" else "mlp"):
        if t == "rwkv6":
            y, new_cache = rec_mod.apply_rwkv_channel_mix(
                p["mlp"], cfg, h, new_cache, ctx.mode,
                active=_active_mask(ctx))
        elif is_moe:
            y, aux = moe_mod.apply_moe(p["moe"], cfg, h)
            if cfg.n_shared_experts:
                y = y + apply_mlp(p["shared"], cfg, h)
        else:
            y = apply_mlp(p["mlp"], cfg, h)
    x = x + y
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# Super-blocks and segments
# ---------------------------------------------------------------------------


def init_superblock(key: jax.Array, cfg: ModelConfig,
                    kinds: tuple[LayerKind, ...]) -> dict:
    ks = jax.random.split(key, len(kinds))
    return {f"sub{i}": init_layer(ks[i], cfg, kind)
            for i, kind in enumerate(kinds)}


def apply_superblock(p: dict, cfg: ModelConfig, kinds: tuple[LayerKind, ...],
                     x: jax.Array, caches: Any, ctx: ModelCtx):
    new_caches = {}
    aux = moe_mod.zero_stats()
    for i, kind in enumerate(kinds):
        c = None if caches is None else caches[f"sub{i}"]
        x, nc, a = apply_layer(p[f"sub{i}"], cfg, kind, x, c, ctx)
        aux = jax.tree.map(jnp.add, aux, a)
        new_caches[f"sub{i}"] = nc
    return x, (None if caches is None else new_caches), aux


def init_segment(key: jax.Array, cfg: ModelConfig, seg: Segment,
                 captured_axes: dict) -> Any:
    """Returns the segment's value tree; records the axes tree (with a
    leading 'layers' axis for scanned segments) into ``captured_axes``."""

    def vals_fn(k):
        tree = init_superblock(k, cfg, seg.kinds)
        vals, axes = split(tree)
        captured_axes["axes"] = axes
        return vals

    if seg.scanned:
        vals = jax.vmap(vals_fn)(jax.random.split(key, seg.repeats))
        captured_axes["axes"] = jax.tree.map(
            lambda a: ("layers",) + a, captured_axes["axes"],
            is_leaf=lambda a: isinstance(a, tuple)
            and all(isinstance(e, (str, type(None))) for e in a))
    else:
        vals = vals_fn(key)
    return vals


def segment_cache_specs(cfg: ModelConfig, seg: Segment, batch: int,
                        max_len: int, enc_len: int, dtype,
                        pages: tuple[int, int] | None = None) -> Any:
    per_block = {
        f"sub{i}": cache_specs_for_kind(cfg, kind, batch, max_len, enc_len,
                                        dtype, pages=pages)
        for i, kind in enumerate(seg.kinds)
    }
    if not seg.scanned:
        return per_block

    def stack(leaf):
        sds, axes = leaf
        return (jax.ShapeDtypeStruct((seg.repeats,) + sds.shape, sds.dtype),
                (None,) + tuple(axes))

    return jax.tree.map(stack, per_block,
                        is_leaf=lambda x: isinstance(x, tuple) and len(x) == 2
                        and isinstance(x[0], jax.ShapeDtypeStruct))


def _is_axes_leaf(a: Any) -> bool:
    return (isinstance(a, tuple)
            and all(isinstance(e, (str, type(None))) for e in a))


def _constrain_layer_params(p_layer: Any, axes: Any, scanned: bool) -> Any:
    """Pin each per-layer weight slice to its (TP x FSDP) shard layout inside
    the scan body.  The transpose of a sharding constraint is the same
    constraint, so the *gradient* of each weight is forced to the sharded
    layout right where it is produced — XLA then lowers the data-axis batch
    reduction as reduce-scatter instead of a full all-reduce + slice
    (EXPERIMENTS.md §Perf iteration 3)."""
    from repro.distributed.sharding import constrain

    if axes is None:
        return p_layer

    def apply(v, ax):
        ax = tuple(ax[1:]) if scanned else tuple(ax)
        return constrain(v, *ax)

    return jax.tree.map(apply, p_layer, axes)


def apply_segment(p: Any, cfg: ModelConfig, seg: Segment, x: jax.Array,
                  caches: Any, ctx: ModelCtx, axes: Any = None):
    if not seg.scanned:
        p = _constrain_layer_params(p, axes, scanned=False)
        return apply_superblock(p, cfg, seg.kinds, x, caches, ctx)

    fn = functools.partial(apply_superblock, cfg=cfg, kinds=seg.kinds, ctx=ctx)
    if ctx.mode == "train" and cfg.remat != "none":
        policy = (jax.checkpoint_policies.nothing_saveable
                  if cfg.remat == "full"
                  else jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
        fn = jax.checkpoint(fn, policy=policy)

    if caches is None:
        def body(carry, p_layer):
            x_, aux_ = carry
            p_layer = _constrain_layer_params(p_layer, axes, scanned=True)
            x_, _, a = fn(p_layer, x=x_, caches=None)
            return (x_, jax.tree.map(jnp.add, aux_, a)), None

        (x, aux), _ = jax.lax.scan(body, (x, moe_mod.zero_stats()), p)
        return x, None, aux

    # the stacked caches ride in the carry: as scan outputs they would be a
    # second copy of every cache (a whole page pool per layer), which does
    # not fit one chip.  A paged sub-layer reads and writes its stacked pool
    # in place at ctx.layer; every other cache is sliced out for the layer
    # and its slice written back.
    in_place = {f"sub{j}": _paged(cfg, kind, ctx)
               for j, kind in enumerate(seg.kinds)}

    def body(carry, xs):
        x_, aux_, caches_ = carry
        i, p_layer = xs
        p_layer = _constrain_layer_params(p_layer, axes, scanned=True)
        with jax.named_scope("kv_pool"):
            cache_layer = {s: c if in_place[s] else jax.tree.map(
                lambda a: a[i], c) for s, c in caches_.items()}
        x_, nc, a = fn(p_layer, x=x_, caches=cache_layer,
                       ctx=dataclasses.replace(ctx, layer=i))
        with jax.named_scope("kv_pool"):
            caches_ = {s: nc[s] if in_place[s] else jax.tree.map(
                lambda c, n: c.at[i].set(n.astype(c.dtype)), c, nc[s])
                for s, c in caches_.items()}
        return (x_, jax.tree.map(jnp.add, aux_, a), caches_), None

    (x, aux, new_caches), _ = jax.lax.scan(
        body, (x, moe_mod.zero_stats(), caches),
        (jnp.arange(seg.repeats), p))
    return x, new_caches, aux
