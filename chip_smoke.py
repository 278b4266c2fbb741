"""Bring-up smoke test: the system's main paths, once each, on the chip.

    python chip_smoke.py [--seed 0]         # one TPU chip
    python chip_smoke.py --four-chips       # one host with four chips (2x2)

On one chip it runs three phases in this one process:

* kernels: both Pallas kernels compiled (not interpreted) at granite's
  attention widths and rwkv6's WKV widths, against ``kernels/ref.py``;
* serve: ``PagedServingEngine`` with granite-moe-1b-a400m at its published
  widths and all 24 layers, 8 mixed-length requests, with the logits after
  chunked paged prefill checked against the model's own full forward pass;
* orchestration: the paper's §5 CommonCrawl graph materialized through
  ``RunCoordinator`` at its Table-1 partitioning, with a profiler trace
  showing the asset bodies' operations on the TPU.

``--four-chips`` runs only one sharded train step of granite on a
(data 2, model 2) mesh, compared with the same step on one chip.

Times, compile seconds and memory are printed as bring-up readings, not as
benchmark metrics.  The script exits non-zero, and prints no result line,
when JAX finds no TPU or any phase fails.  Otherwise the last line of
standard output is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import gc
import glob
import json
import os
import shutil
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.cc_pipeline import PARTS, build_graph  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core import (CostModel, DynamicClientFactory,  # noqa: E402
                        Objective, RunCoordinator, default_catalog)
from repro.core.partitions import partition_keys  # noqa: E402
from repro.data.commoncrawl import CrawlConfig  # noqa: E402
from repro.distributed.sharding import MeshInfo, use_mesh_info  # noqa: E402
from repro.kernels.flash_attention import flash_attention  # noqa: E402
from repro.kernels.linear_scan import linear_scan  # noqa: E402
from repro.kernels.ref import attention_ref, wkv_ref  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.launch.serve import PagedServingEngine, Request  # noqa: E402
from repro.launch.train import make_train_step  # noqa: E402
from repro.models import LanguageModel  # noqa: E402
from repro.models import moe as moe_mod  # noqa: E402
from repro.models.attention import ModelCtx  # noqa: E402
from repro.optim import AdamW, OptConfig  # noqa: E402
from repro.utils import enable_compile_cache  # noqa: E402

ARCH = "granite-moe-1b-a400m"
# every rung of the 64-token chunk ladder (64, 32, ..., 1) is hit at least
# once by the greedy decomposition of these lengths
PROMPT_LENS = (6, 17, 64, 100, 300, 700, 1000, 1500)
PARITY_RIDS = (3, 7)  # the 100- and 1500-token prompts: 3 and 26 chunks
# bf16 compute: chunked paged prefill and the full forward round the same
# bf16 activations through different attention blockings and batch
# compositions, and 24 residual layers carry that rounding (2^-8 relative
# per op) into the logits.  Bounded as a share of the logits' spread.
LOGIT_TOL = 0.05
# edges' token-overlap tensor is (E, 128, 128) bool = 16 KiB per edge: at
# ~20 links per seed, 4096 seeds give E ~ 80k, i.e. > 1 GiB per partition
SMOKE_CRAWL = CrawlConfig(n_domains=1024, n_pages_per_domain=16, n_seed=4096,
                          max_links=40, tokens_per_page=128, vocab=4096)
TRACE_DIR = os.path.join(ROOT, "artifacts", "smoke_trace")  # git-ignored


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, and persistent
    cache hits, read from ``jax.monitoring`` events."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self) -> None:
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_) -> None:
        if event in self.EVENTS:
            self.seconds += duration

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def _close(got, want, rtol: float, atol: float, what: str) -> None:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    assert got.shape == want.shape, (what, got.shape, want.shape)
    assert np.isfinite(got).all(), f"{what}: non-finite output"
    log(f"[kernels] {what}: max |diff| {np.max(np.abs(got - want))}, "
        f"max |ref| {np.max(np.abs(want))}, bound rtol {rtol} atol {atol}")
    np.testing.assert_allclose(got, want, rtol=rtol, atol=atol, err_msg=what)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def phase_kernels(seed: int) -> None:
    """Both kernels compiled for the chip, against the plain references
    computed at "highest" matmul precision.  Attention keeps the kernels'
    own interpret-mode test tolerances (tests/test_kernels.py)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 9)
    # granite's attention: 16 query heads over 8 KV heads of 64
    B, S, Hq, Hkv, D = 2, 2048, 16, 8, 64
    for dtype, tol in ((jnp.bfloat16, 2e-2), (jnp.float32, 2e-5)):
        q = jax.random.normal(ks[0], (B, S, Hq, D), dtype)
        k = jax.random.normal(ks[1], (B, S, Hkv, D), dtype)
        v = jax.random.normal(ks[2], (B, S, Hkv, D), dtype)
        out = jax.jit(lambda q, k, v: flash_attention(
            q, k, v, causal=True, interpret=False))(q, k, v)
        with jax.default_matmul_precision("highest"):
            ref = jax.jit(attention_ref)(q, k, v)
        _close(out, ref, tol, tol,
               f"flash_attention {jnp.dtype(dtype).name} B{B} S{S} "
               f"H{Hq}/{Hkv} D{D}")
    # rwkv6-1.6b's WKV: 32 heads of N = 64, realistic decays
    B, S, H, N = 2, 1024, 32, 64
    r, k, v = (jax.random.normal(kk, (B, S, H, N), jnp.float32)
               for kk in ks[3:6])
    log_w = -jnp.exp(jax.random.uniform(ks[6], (B, S, H, N), jnp.float32,
                                        -6.0, 0.0))
    u = jax.random.normal(ks[7], (H, N), jnp.float32) * 0.1
    s0 = jax.random.normal(ks[8], (B, H, N, N), jnp.float32) * 0.5
    y, s_fin = jax.jit(lambda *a: linear_scan(*a, interpret=False))(
        r, k, v, log_w, u, s0)
    with jax.default_matmul_precision("highest"):
        y_ref, s_ref = jax.jit(wkv_ref)(r, k, v, log_w, u, s0)
    # f32 throughout, but the chip's exp and the chunked sums round apart
    # from the step-by-step reference, and 1024 steps of state carry that:
    # the absolute bound scales with the output (a flat 1e-4 failed on a
    # v5e by up to 5e-4), the relative one stays the interpret-mode 1e-4
    for got, want, what in ((y, y_ref, f"y B{B} S{S} H{H} N{N}"),
                            (s_fin, s_ref, f"s_fin B{B} H{H} N{N}")):
        scale = float(jnp.max(jnp.abs(want)))
        _close(got, want, 1e-4, 1e-4 * scale, f"linear_scan {what}")


def full_forward_last_logits(model: LanguageModel):
    """The model's own full forward pass (no cache, no chunking), logits at
    the last position: the comparison of tests/test_decode_parity.py."""
    def f(params, tokens):
        B, S = tokens.shape
        ctx = ModelCtx(mode="train", positions=model._positions(B, S, None))
        x = model._embed(params, tokens)
        x, _, _ = model._backbone(params, x, None, ctx)
        return model._head(params, x[:, -1:])[:, 0]
    return jax.jit(f)


def phase_serve(seed: int, cfg=None, n_slots: int = 32, max_len: int = 2048,
                page_size: int = 16, prompt_lens=PROMPT_LENS,
                parity_rids=PARITY_RIDS, max_new: int = 32) -> dict:
    # served weights are held in bf16, the compute dtype: float32 master
    # weights (5.3 GB) and their per-call bf16 copies do not fit one v5e
    # beside the 3.2 GB page pool
    cfg = cfg or get_config(ARCH).scaled(param_dtype="bfloat16")
    model = LanguageModel(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    eng = PagedServingEngine(model, params, n_slots=n_slots, max_len=max_len,
                             page_size=page_size)
    log(f"[serve] {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.n_heads}/{cfg.n_kv_heads} heads of {cfg.head_dim}, "
        f"{cfg.n_experts} experts top-{cfg.top_k}, vocab {cfg.vocab_size}, "
        f"{cfg.param_dtype} weights; {n_slots} slots x {max_len} tokens, "
        f"pages of {page_size}")

    # keep the logits each request's last prefill chunk hands to the
    # engine's finalize step (the seed of its first emitted token)
    prefill_logits: dict[int, jax.Array] = {}
    finalize = eng._finalize

    def recording_finalize(last, pos, remaining, logits, slot, *rest):
        prefill_logits[eng.slot_req[int(slot)].rid] = logits[0]
        return finalize(last, pos, remaining, logits, slot, *rest)

    eng._finalize = recording_finalize

    rng = np.random.RandomState(seed)
    prompts = [rng.randint(0, cfg.vocab_size, n).tolist() for n in prompt_lens]

    def requests():
        return [Request(rid=i, prompt=p, max_new=max_new)
                for i, p in enumerate(prompts)]

    cold_reqs = requests()
    cold = eng.run(cold_reqs)
    assert cold["rejected"] == 0, cold
    for r in cold_reqs:
        assert not r.rejected and r.done, r.rid
        assert len(r.out) == max_new, (r.rid, len(r.out))
        assert all(0 <= t < cfg.vocab_size for t in r.out), r.rid
    warm_reqs = requests()
    warm = eng.run(warm_reqs)
    assert [r.out for r in warm_reqs] == [r.out for r in cold_reqs], \
        "a warm rerun of the same requests emitted different tokens"
    log(f"[serve] {len(cold_reqs)} requests, prompts {list(prompt_lens)}, "
        f"{cold['tokens']} tokens, 0 rejected; "
        f"{cold['prefill_chunks']} prefill chunks, "
        f"{cold['decode_ticks']} decode ticks")

    forward = full_forward_last_logits(model)
    for rid in parity_rids:
        got = np.asarray(prefill_logits[rid], np.float32)
        ref = np.asarray(forward(params, jnp.asarray([prompts[rid]]))[0],
                         np.float32)
        assert np.isfinite(got).all(), f"request {rid}: non-finite logits"
        rel = float(np.sqrt(np.mean((got - ref) ** 2) / np.mean(ref ** 2)))
        log(f"[serve] request {rid} ({len(prompts[rid])} tokens): chunked "
            f"paged prefill vs full forward: relative rms error {rel} "
            f"(bound {LOGIT_TOL}), max |diff| {float(np.abs(got - ref).max())}"
            f", logit std {float(np.std(ref))}, argmax {int(got.argmax())} "
            f"vs {int(ref.argmax())}")
        assert rel <= LOGIT_TOL, f"request {rid}: logits diverged"
    return {"cold_wall_s": cold["wall_s"], "warm_wall_s": warm["wall_s"]}


def device_op_events(trace_dir: str) -> int:
    """Operations the profiler saw on TPU devices (0 means the traced work
    never reached the chip)."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    assert paths, f"no profiler trace under {trace_dir}"
    n = 0
    for path in paths:
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            if plane.name.startswith("/device:TPU"):
                n += sum(1 for line in plane.lines for _ in line.events)
    return n


def phase_orchestration(seed: int, crawl: CrawlConfig = SMOKE_CRAWL,
                        partitions=PARTS, min_overlap_bytes: int = 1 << 30,
                        trace_dir: str = TRACE_DIR) -> None:
    log(f"[orchestration] {crawl}")
    graph = build_graph(cfg=crawl, partitions=partitions)
    keys = partition_keys(partitions)
    factory = DynamicClientFactory(default_catalog(), CostModel(),
                                   Objective.balanced(), sim_seed=seed,
                                   sim_time_scale=0.0)
    coord = RunCoordinator(graph, factory)
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 0
    t0 = time.perf_counter()
    with jax.profiler.trace(trace_dir, profiler_options=opts):
        report = coord.materialize(["graph_aggr"], run_id=f"smoke-{seed}")
    wall = time.perf_counter() - t0

    assert report.ok, report.summary()
    assert len(report.records) == 4 * len(keys), len(report.records)
    assert all(r.status == "success" for r in report.records)
    # every failed attempt must be one the simulated platforms injected: an
    # asset body that raised is a fault, even if a retry later succeeded
    attempts = [a for r in report.records for a in r.attempts]
    faults = [a.error for a in attempts
              if a.error and ": injected " not in a.error]
    assert not faults, faults
    retried = sum(a.status != "success" for a in attempts)
    overlap = {k: len(coord.store.get("edges", k)["src"]) * 128 * 128
               for k in keys}
    small = {k: b for k, b in overlap.items() if b < min_overlap_bytes}
    assert not small, f"edges overlap tensor under {min_overlap_bytes} B: " \
                      f"{small}"
    n_ops = device_op_events(trace_dir)
    assert n_ops > 0, "no operation of the traced run reached the TPU"
    log(f"[orchestration] {len(report.records)} tasks over {len(keys)} "
        f"partitions ok, {len(attempts)} attempts ({retried} injected "
        f"failures retried); edges overlap tensor "
        f"{min(overlap.values()) / 2**30}-{max(overlap.values()) / 2**30} "
        f"GiB per partition; {n_ops} device operations traced on the TPU; "
        f"run wall {wall} s (traced, bring-up reading)")


def phase_sharded_train(seed: int, n_layers: int = 4, batch: int = 8,
                        seq: int = 1024) -> None:
    """One train step on a (data 2, model 2) mesh of four chips against the
    same step on one chip.  Capacity covers every local token
    (capacity_factor = n_experts / top_k), so the shard_map path may drop
    nothing, as the one-chip dense path drops nothing."""
    assert len(jax.devices()) == 4, jax.devices()
    base = get_config(ARCH)
    cfg = base.scaled(n_layers=n_layers,
                      capacity_factor=base.n_experts / base.top_k)
    log(f"[train4] {cfg.name} at published widths, depth cut "
        f"{base.n_layers} -> {cfg.n_layers} layers, capacity_factor "
        f"{cfg.capacity_factor}; batch {batch} x {seq} tokens")
    model = LanguageModel(cfg)
    opt = AdamW(OptConfig())
    rng = np.random.RandomState(seed)
    data = {"tokens": rng.randint(0, cfg.vocab_size, (batch, seq)),
            "targets": rng.randint(0, cfg.vocab_size, (batch, seq)),
            "weights": np.ones((batch, seq), np.float32)}
    # both steps donate their parameters: each gets its own copy, made from
    # the same seed on one chip
    init = jax.jit(model.init)
    params, params_4 = (init(jax.random.PRNGKey(seed)) for _ in range(2))

    info = MeshInfo(make_mesh((2, 2), ("data", "model")))
    with use_mesh_info(info), info.mesh:
        assert moe_mod._shard_map_viable(
            cfg, jax.ShapeDtypeStruct((batch, seq, cfg.d_model),
                                      jnp.bfloat16)), "shard_map path off"
        params_s = jax.device_put(params_4, jax.tree.map(
            lambda v, ax: info.sharding(v.shape, ax), params_4,
            model.param_axes))
        batch_s = jax.device_put(data, {
            k: info.sharding(v.shape, ("batch", "seq_act"))
            for k, v in data.items()})
        opt_s = opt.init(params_s)
        step = make_train_step(model, opt).lower(params_s, opt_s,
                                                 batch_s).compile()
        assert "all-to-all" in step.as_text(), "no expert all-to-all"
        _, _, m4 = step(params_s, opt_s, batch_s)

    _, _, m1 = make_train_step(model, opt)(
        params, opt.init(params), {k: jnp.asarray(v) for k, v in data.items()})
    m1, m4 = jax.device_get((m1, m4))
    log(f"[train4] loss one chip {float(m1['loss'])} vs four "
        f"{float(m4['loss'])}; grad_norm {float(m1['grad_norm'])} vs "
        f"{float(m4['grad_norm'])}; dropped assignments one chip "
        f"{float(m1['moe_dropped'])}, four {float(m4['moe_dropped'])}")
    assert float(m1["moe_dropped"]) == 0.0 == float(m4["moe_dropped"])
    # bf16 compute: the mesh changes reduction orders (psum over data,
    # expert all-to-all), not the mathematics; over 8k tokens the mean loss
    # agrees to well under 1e-3 relative, the gradient norm to 1e-2
    np.testing.assert_allclose(float(m4["loss"]), float(m1["loss"]),
                               rtol=1e-3)
    np.testing.assert_allclose(float(m4["grad_norm"]),
                               float(m1["grad_norm"]), rtol=1e-2)


# ---------------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded train-step phase on 4 chips")
    args = ap.parse_args()

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (device 0 is {dev.platform!r}); "
              f"nothing was run", file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    clock = CompileClock()
    log(f"[device] {dev.platform} {dev.device_kind} x {len(devices)}; "
        f"compile cache {cache_dir}")

    phases = ([("train4", lambda: phase_sharded_train(args.seed))]
              if args.four_chips else
              [("kernels", lambda: phase_kernels(args.seed)),
               ("serve", lambda: phase_serve(args.seed)),
               ("orchestration", lambda: phase_orchestration(args.seed))])
    failed = []
    for name, fn in phases:
        c0, t0 = clock.seconds, time.perf_counter()
        try:
            out = fn() or {}
        except Exception:
            traceback.print_exc()
            failed.append(name)
            log(f"[{name}] FAILED")
            continue
        finally:
            # the engine and its jitted closures form reference cycles: free
            # the weights and the page pool before the next phase allocates
            gc.collect()
        readings = {"wall_s": time.perf_counter() - t0,
                    "compile_s": clock.seconds - c0,
                    "peak_bytes_in_use_so_far":
                        (dev.memory_stats() or {}).get("peak_bytes_in_use"),
                    **out}
        log(f"[{name}] ok; bring-up readings: {readings}")
    stats = dev.memory_stats() or {}
    log(f"[device] peak_bytes_in_use {stats.get('peak_bytes_in_use')} "
        f"(bring-up reading); {clock.cache_hits} persistent cache hits")
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
