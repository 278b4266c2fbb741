"""Mixture-of-Experts: top-k router + capacity dispatch + expert parallelism.

Three execution paths with identical math (parity-tested):

* ``_moe_dense``  — per-expert einsum over *all* tokens for the experts this
  device holds; used on a single device (unit tests, serving) and as the
  small-T GSPMD path for decode shapes, where tokens are few (<= _SMALL_T)
  and a capacity all-to-all would be all overhead.  With a mesh active,
  experts stay sharded over the model axis and XLA inserts one psum for the
  combine.
* ``_moe_shard_map`` — the production train/prefill path: GShard-style
  capacity buffers, explicit ``all_to_all`` over the model ("expert") axis,
  FSDP all-gather of expert weights over the data axis, scatter-dispatch /
  gather-combine.  Tokens over (pod, data) x seq over model.

Router: softmax over all ``n_experts`` -> (group-limited) top-k -> gates
renormalised, or scaled by ``routed_scaling_factor``; standard
load-balancing aux loss (Switch/GShard).  Over-capacity tokens are dropped
(residual passes through), matching GShard semantics.

Held experts: a layer may hold only a contiguous range of the router's
experts (``cfg.held_experts``), as one device of an expert-parallel
deployment does.  The router keeps its width over all of them; the layer
computes, for each token, the part of the routed sum that its own experts
give, and nothing for the rest.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.distributed.sharding import constrain, current_mesh_info, shard_map_specs
from repro.models.layers import Param, dense_init

_SMALL_T = 4096  # global token threshold below which dense path wins


def zero_stats() -> dict:
    """A layer's MoE statistics, summed over layers: the router's
    load-balance loss and the (token, expert) assignments dropped for lack of
    capacity (always 0 on the dense path)."""
    return {"load_balance": jnp.zeros((), jnp.float32),
            "dropped": jnp.zeros((), jnp.float32)}


def init_moe(key: jax.Array, cfg: ModelConfig) -> dict:
    """The router over all ``n_experts``; the weights of the held ones."""
    d, e, ff = cfg.d_model, cfg.n_experts, cfg.d_ff_expert
    held = cfg.n_held_experts
    dt = cfg.param_dtype
    ks = jax.random.split(key, 5)
    p = {
        "router": Param(dense_init(ks[0], (d, e), 1, dt), ("embed_fsdp", None)),
        "w_gate": Param(dense_init(ks[1], (held, d, ff), 2, dt),
                        ("experts", "embed_fsdp", None)),
        "w_up": Param(dense_init(ks[2], (held, d, ff), 2, dt),
                      ("experts", "embed_fsdp", None)),
        "w_down": Param(dense_init(ks[3], (held, ff, d), 2, dt),
                        ("experts", "expert_ff_fsdp", None)),
    }
    return p


def _act(cfg: ModelConfig, x: jax.Array) -> jax.Array:
    return jax.nn.gelu(x, approximate=True) if cfg.act == "gelu" else jax.nn.silu(x)


def _route(router_w: jax.Array, x2d: jax.Array, cfg: ModelConfig):
    """gates/top-k/aux from router logits over all ``n_experts``.
    x2d: (T, d)."""
    logits = (x2d @ router_w.astype(jnp.float32)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)  # (T, E)
    scores = probs
    if cfg.n_group > 1:
        # group-limited greedy: keep the topk_group groups whose best expert
        # scores highest, zero the others' scores, then take the top-k
        t = probs.shape[0]
        grouped = probs.reshape(t, cfg.n_group, -1)
        _, keep = jax.lax.top_k(grouped.max(-1), cfg.topk_group)
        mask = jnp.zeros((t, cfg.n_group), bool).at[
            jnp.arange(t)[:, None], keep].set(True)
        scores = jnp.where(mask[:, :, None], grouped, 0.0).reshape(t, -1)
    gates, idx = jax.lax.top_k(scores, cfg.top_k)  # (T, K)
    if cfg.norm_topk_prob:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    else:
        gates = gates * cfg.routed_scaling_factor
    # Switch-style load balance loss: E * sum_e f_e * p_e
    e = cfg.n_experts
    me = jnp.mean(probs, axis=0)  # (E,) mean router prob
    assign = jax.nn.one_hot(idx[:, 0], e, dtype=jnp.float32)  # top-1 fraction
    fe = jnp.mean(assign, axis=0)
    aux = e * jnp.sum(fe * me)
    return gates, idx, aux


# ---------------------------------------------------------------------------
# dense / small-T path
# ---------------------------------------------------------------------------


def _moe_dense(p: dict, cfg: ModelConfig, x: jax.Array) -> tuple[jax.Array, dict]:
    cdt = cfg.compute_dtype
    B, S, d = x.shape
    x2d = x.reshape(-1, d)
    gates, idx, aux = _route(p["router"], x2d, cfg)
    # held-experts compute (T small): h (T, E_held, ff), E sharded over model
    h = jnp.einsum("td,edf->tef", x2d, p["w_gate"].astype(cdt))
    u = jnp.einsum("td,edf->tef", x2d, p["w_up"].astype(cdt))
    h = _act(cfg, h) * u
    h = constrain(h, None, "experts", None)
    y_e = jnp.einsum("tef,efd->ted", h, p["w_down"].astype(cdt))
    y_e = constrain(y_e, None, "experts", None)
    # combine: sum_k gate_k * y_e[t, idx_k] over the k whose expert is held
    # (one_hot of an index outside [0, E_held) is all zeros)
    lo, _ = cfg.held_range
    if lo:
        idx = idx - lo
    sel = jax.nn.one_hot(idx, cfg.n_held_experts, dtype=cdt)  # (T, K, E_held)
    w_comb = jnp.einsum("tk,tke->te", gates.astype(cdt), sel)  # (T, E_held)
    y = jnp.einsum("te,ted->td", w_comb, y_e)
    return y.reshape(B, S, d), {**zero_stats(), "load_balance": aux}


# ---------------------------------------------------------------------------
# shard_map capacity-dispatch path
# ---------------------------------------------------------------------------


def _capacity(tokens_local: int, cfg: ModelConfig) -> int:
    c = int(tokens_local * cfg.top_k * cfg.capacity_factor / cfg.n_experts)
    return max(8, ((c + 7) // 8) * 8)


def _dispatch_compute_combine(
    x_l: jax.Array,  # (b_l, s_l, d) local tokens
    router_l: jax.Array,  # (d_shard, E)
    wg_l: jax.Array,  # (E_l, d_shard, ff)
    wu_l: jax.Array,
    wd_l: jax.Array,  # (E_l, ff_shard, d)
    *,
    cfg: ModelConfig,
    data_axis: str | None,
    model_axis: str,
    all_axes: tuple,
) -> tuple[jax.Array, dict]:
    cdt = cfg.compute_dtype
    b_l, s_l, d = x_l.shape
    E = cfg.n_experts

    # FSDP gathers (weights stored sharded over the data axis)
    if data_axis is not None:
        router_w = jax.lax.all_gather(router_l, data_axis, axis=0, tiled=True)
        w_gate = jax.lax.all_gather(wg_l, data_axis, axis=1, tiled=True)
        w_up = jax.lax.all_gather(wu_l, data_axis, axis=1, tiled=True)
        w_down = jax.lax.all_gather(wd_l, data_axis, axis=1, tiled=True)
    else:
        router_w, w_gate, w_up, w_down = router_l, wg_l, wu_l, wd_l

    x2d = x_l.reshape(-1, d)  # (T_l, d)
    t_l = x2d.shape[0]
    gates, idx, aux = _route(router_w, x2d, cfg)
    cap = _capacity(t_l, cfg)

    # position of each (token, slot) within its expert buffer
    flat_e = idx.reshape(-1)  # (T_l*K,) row-major (t, k)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)  # (T_l*K, E)
    pos = jnp.cumsum(onehot, axis=0) - onehot  # exclusive count
    pos = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]  # (T_l*K,)
    keep = pos < cap
    pos_c = jnp.minimum(pos, cap - 1)

    x_rep = jnp.repeat(x2d, cfg.top_k, axis=0)  # (T_l*K, d)
    val = jnp.where(keep[:, None], x_rep.astype(cdt), 0)
    buf = jnp.zeros((E, cap, d), cdt).at[flat_e, pos_c].add(val)

    # expert-parallel exchange: (E, cap, d) -> (E_l, cap * ep, d)
    buf = jax.lax.all_to_all(buf, model_axis, split_axis=0, concat_axis=1,
                             tiled=True)
    h = jnp.einsum("ecd,edf->ecf", buf, w_gate.astype(cdt))
    u = jnp.einsum("ecd,edf->ecf", buf, w_up.astype(cdt))
    y = jnp.einsum("ecf,efd->ecd", _act(cfg, h) * u, w_down.astype(cdt))
    y = jax.lax.all_to_all(y, model_axis, split_axis=1, concat_axis=0,
                           tiled=True)  # back to (E, cap, d)

    # combine: gather back per (token, slot), weight by gates, drop overflow
    picked = y[flat_e, pos_c]  # (T_l*K, d)
    picked = jnp.where(keep[:, None], picked, 0)
    out = (picked.reshape(t_l, cfg.top_k, d)
           * gates.astype(cdt)[..., None]).sum(axis=1)
    stats = {"load_balance": jax.lax.pmean(aux, all_axes),
             "dropped": jax.lax.psum(jnp.sum(~keep).astype(jnp.float32),
                                     all_axes)}
    return out.reshape(b_l, s_l, d), stats


def _moe_shard_map(p: dict, cfg: ModelConfig, x: jax.Array) -> tuple[jax.Array, dict]:
    if cfg.n_held_experts != cfg.n_experts:
        raise ValueError(
            f"{cfg.name}: the capacity dispatch shards all {cfg.n_experts} "
            f"experts over the mesh; this layer holds {cfg.n_held_experts}")
    info = current_mesh_info()
    data_axes, model_axis = shard_map_specs(info)
    mesh = info.mesh
    data_axis = "data" if "data" in mesh.axis_names else None
    batch_spec = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    bs = batch_spec[0] if len(batch_spec) == 1 else batch_spec

    fn = functools.partial(
        _dispatch_compute_combine,
        cfg=cfg,
        data_axis=data_axis,
        model_axis=model_axis,
        all_axes=tuple(mesh.axis_names),
    )
    # check_vma off: the dispatch body's all_gather/all_to_all pattern
    # defeats the varying-manual-axes checker
    out, stats = jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(
            P(bs, "model", None),  # x: batch over DP axes, seq over model
            P("data", None),  # router
            P("model", "data", None),  # w_gate
            P("model", "data", None),  # w_up
            P("model", "data", None),  # w_down
        ),
        out_specs=(P(bs, "model", None), P()),
        check_vma=False,
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    return out, stats


def _shard_map_viable(cfg: ModelConfig, x: jax.Array) -> bool:
    info = current_mesh_info()
    if info is None or "model" not in info.mesh.axis_names:
        return False
    B, S, _ = x.shape
    if B * S <= _SMALL_T:
        return False
    mdl = info.axis_size("model")
    dp = info.axis_size("data") * info.axis_size("pod")
    return (B % dp == 0 and S % mdl == 0 and cfg.n_experts % mdl == 0
            and cfg.d_model % info.axis_size("data") == 0)


def apply_moe(p: dict, cfg: ModelConfig, x: jax.Array) -> tuple[jax.Array, dict]:
    if _shard_map_viable(cfg, x):
        return _moe_shard_map(p, cfg, x)
    return _moe_dense(p, cfg, x)
