"""Jit'd dispatch wrappers for the Pallas kernels.

On TPU the compiled kernels run natively.  Elsewhere the same kernel bodies
run only when the caller passes ``interpret=True`` (the Pallas interpreter,
for correctness work): left at ``None`` on a backend other than TPU, a call
raises instead of silently interpreting.

Tile selection (``kernels/autotune.py``) happens *outside* the jit boundary
so the blocks reach ``pallas_call`` as static values:

* explicit ``block_q=``/``block_k=``/``chunk=`` kwargs always win and never
  consult the tuner;
* ``tuned=True`` resolves the shape/dtype/backend key against the autotune
  cache — a hit (including entries shipped via the committed baseline store)
  costs zero timing work; a miss on a compiled-TPU host runs the timing
  search once and persists the winner; interpret mode, non-TPU hosts and
  in-trace calls fall back to the VMEM/head-dim heuristic instead of timing;
* ``tuned=False`` (default) keeps the fixed historical defaults.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels import autotune as _at
from repro.kernels import flash_attention as _fa
from repro.kernels import linear_scan as _ls
from repro.kernels import ref as _ref

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
DEFAULT_CHUNK = 64


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _interpret(interpret: bool | None) -> bool:
    if interpret is not None:
        return interpret
    if not _on_tpu():
        raise ValueError(
            f"Pallas kernels compile only for TPU, and the backend is "
            f"{jax.default_backend()!r}: pass interpret=True to run them in "
            f"the Pallas interpreter")
    return False


def _can_time(*arrays) -> bool:
    """Eager concrete arrays only: a timing search cannot run under trace."""
    return not any(isinstance(a, jax.core.Tracer) for a in arrays)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "scale", "q_offset", "block_q", "block_k",
    "out_scale", "interpret"))
def _flash_jit(q, k, v, residual, *, causal, window, scale, q_offset,
               block_q, block_k, out_scale, interpret):
    return _fa.flash_attention(
        q, k, v, causal=causal, window=window, scale=scale,
        q_offset=q_offset, block_q=block_q, block_k=block_k,
        out_scale=out_scale, residual=residual, interpret=interpret)


def flash_attention(q, k, v, *, causal=True, window=0, scale=None,
                    q_offset=0, block_q=None, block_k=None, tuned=False,
                    out_scale=1.0, residual=None, interpret=None):
    interp = _interpret(interpret)
    bq, bk = block_q, block_k
    if tuned and (bq is None or bk is None):
        cfg = _resolve_attention(q, k, v, causal=causal, window=window,
                                 q_offset=q_offset, scale=scale,
                                 interpret=interp,
                                 has_residual=residual is not None)
        bq = bq if bq is not None else cfg["block_q"]
        bk = bk if bk is not None else cfg["block_k"]
    return _flash_jit(q, k, v, residual, causal=causal, window=window,
                      scale=scale, q_offset=q_offset,
                      block_q=bq if bq is not None else DEFAULT_BLOCK_Q,
                      block_k=bk if bk is not None else DEFAULT_BLOCK_K,
                      out_scale=out_scale, interpret=interp)


def _resolve_attention(q, k, v, *, causal, window, q_offset, scale,
                       interpret, has_residual):
    tuner = _at.get_tuner()
    key = _at.attention_key(q.shape, k.shape, v.shape, q.dtype,
                            causal=causal, window=window,
                            backend=_at.backend_tag(interpret))
    B, Sq, Hq, D = q.shape
    _, Skv, _, Dv = v.shape

    def heuristic():
        return _at.heuristic_attention(Sq, Skv, D, Dv, q.dtype)

    if _on_tpu() and not interpret and _can_time(q, k, v):
        hit = tuner.lookup(key)
        if hit is not None and hit.get("mode") != "heuristic":
            return hit["config"]
        cands = _at.attention_candidates(Sq, Skv, D, Dv, q.dtype,
                                         has_residual=has_residual)
        if not cands:
            return heuristic()

        def measure(cfg):
            return _at.measure_us(lambda: _flash_jit(
                q, k, v, residual=None, causal=causal, window=window,
                scale=scale, q_offset=q_offset, block_q=cfg["block_q"],
                block_k=cfg["block_k"], out_scale=1.0, interpret=False))

        return tuner.tune(key, cands, measure, mode="tpu")["config"]
    return tuner.resolve(key, heuristic)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _scan_jit(r, k, v, log_w, u, s0, *, chunk, interpret):
    return _ls.linear_scan(r, k, v, log_w, u, s0, chunk=chunk,
                           interpret=interpret)


def linear_scan(r, k, v, log_w, u, s0, *, chunk=None, tuned=False,
                interpret=None):
    interp = _interpret(interpret)
    c = chunk
    if tuned and c is None:
        c = _resolve_scan(r, k, v, log_w, u, s0, interpret=interp)["chunk"]
    return _scan_jit(r, k, v, log_w, u, s0,
                     chunk=c if c is not None else DEFAULT_CHUNK,
                     interpret=interp)


def _resolve_scan(r, k, v, log_w, u, s0, *, interpret):
    tuner = _at.get_tuner()
    key = _at.scan_key(r.shape, r.dtype, backend=_at.backend_tag(interpret))
    B, S, H, N = r.shape

    def heuristic():
        return _at.heuristic_scan(S, N, r.dtype)

    if _on_tpu() and not interpret and _can_time(r, k, v, log_w, u, s0):
        hit = tuner.lookup(key)
        if hit is not None and hit.get("mode") != "heuristic":
            return hit["config"]
        cands = _at.scan_candidates(S, N, r.dtype)
        if not cands:
            return heuristic()

        def measure(cfg):
            return _at.measure_us(lambda: _scan_jit(
                r, k, v, log_w, u, s0, chunk=cfg["chunk"],
                interpret=False)[0])

        return tuner.tune(key, cands, measure, mode="tpu")["config"]
    return tuner.resolve(key, heuristic)


def paged_attention(q, kp, vp, posp, table, pos_q, *, causal=True, window=0,
                    scale=None, layer=None, dtype=None):
    """Decode attention over a paged KV pool.

    q: (B, 1, Hq, Dk); kp/vp: (n_pages, page_size, Hkv * D) pools;
    posp: (n_pages, page_size) absolute positions (-1 = empty);
    table: (B, max_pages) block table, entries == n_pages = unallocated.
    With ``layer`` the pools are stacked, (layers, n_pages, ...), and the
    pages are gathered from that layer alone; ``dtype`` is what the gathered
    keys and values are cast to.

    Gathers each slot's pages into a contiguous (B, max_pages*page_size, ...)
    view — unallocated pages read as pos == -1 via the gather's fill mode, so
    the position mask in ``attention_core`` drops them exactly.  The gather is
    O(B * max_pages * page_size), i.e. per-slot *capacity*, not pool size:
    slots only ever pay for the pages their own request reserved.
    """
    from repro.models.attention import attention_core  # lazy: avoid cycle

    B, P = table.shape
    ps = posp.shape[-1]
    flat = table.reshape(-1)  # (B*P,)
    at = flat if layer is None else (layer, flat)

    def gather(pool, fill, *heads):
        g = pool.at[at].get(mode="fill", fill_value=fill)  # (B*P, ps, ...)
        return g.reshape(B, P * ps, *heads)

    hkv = kp.shape[-1] // q.shape[-1]
    k, v = gather(kp, 0, hkv, -1), gather(vp, 0, hkv, -1)
    pos_k = gather(posp, -1)
    if dtype is not None:
        k, v = k.astype(dtype), v.astype(dtype)
    return attention_core(q, k, v, pos_q, pos_k, causal=causal,
                          window=window, scale=scale)


# re-exported oracles
attention_ref = _ref.attention_ref
wkv_ref = _ref.wkv_ref
