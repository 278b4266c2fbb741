"""The benchmark: one cell of ``BENCHMARK.json``, one run.

    python3 bench/run.py --workload granite-moe-1b.decode --seed 7 \
        --seconds 30 --trace 0

Set-up makes the weights on the device from the seed, builds the serving
engine, compiles every program the cell's traffic uses and, where the mix
has them, admits the starting population and runs the pre-roll's arrivals
(``bench/traffic.py``).  Then the open loop measures for ``--seconds``, and
serves on only until the requests due in the window have had their first
tokens.  ``--trace 0`` prints the cell's end-to-end metrics;
``--trace 1`` records a profiler trace of the window and prints the per-layer
metrics instead.  After the window the engine is freed and a sample of the
finished requests is compared with the plain reference (``bench/correct.py``).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` with
``--trace 1``), and last ``checks``: each number compared, with its limit.

Everything the run needs is found by name: the configuration file named in
``BENCHMARK.json``, the architecture module ``bench/arch/<name>.py`` that
the file names, ``bench/traffic/<mix>.json`` and
``bench/metrics/<metric>.py`` (a ``read(ctx)`` that returns a number, or
None when it finds nothing to read).

Without a TPU it exits with code 3 and prints no result, unless
``--cpu-rehearsal`` asks to run on the CPU (tests at smoke size only: such a
run measures no device).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

# libtpu would write its logs under /tmp: a fixed path outside the checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402

from bench import adapter, correct, peaks, traffic  # noqa: E402
from bench import arch as archs  # noqa: E402
from bench import trace as trace_mod  # noqa: E402

NO_DEVICE = 3


@dataclasses.dataclass
class Ctx:
    """What a metric's ``read`` sees."""
    loop: adapter.OpenLoop
    m: dict  # the configuration's model sizes
    peaks: peaks.Peaks | None
    reduced: trace_mod.Reduced | None
    setup_s: float
    seconds: float
    drain_every: int
    arch: object = None  # the architecture module (bench/arch): counts


class CompileCounter:
    """Programs compiled or loaded from the persistent cache."""

    def __init__(self) -> None:
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event: str, *_, **__) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1

    def _event(self, event: str, *_, **__) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.n += 1


def load_cell(root: str, workload: str):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"known: {sorted(cells)}")
    cell = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(root, entry["file"])) as f:
        cj = json.load(f)
    mix = traffic.load_mix(root, cell["traffic"])
    return bench, cell, cj, mix, archs.of(cj, root)


def cell_metrics(bench: dict, cell: dict, trace: bool) -> list[dict]:
    kind = "per_layer" if trace else "end_to_end"
    return [m for m in bench[kind]
            if cell["name"] in m.get("workloads", [cell["name"]])]


def read_metric(root: str, name: str, ctx: Ctx):
    path = os.path.join(root, "bench", "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def set_compile_cache(root: str) -> None:
    """JAX's persistent cache at a fixed path inside the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), for every program however small."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class Tracer:
    """Profiler on from the window's opening until the loop has ended, with
    a ``bench_window`` span over exactly the window (the reduction clips to
    it).  The profiler is stopped only after the loop: collecting the trace
    takes seconds, and would otherwise stall the serving that follows the
    window, adding to the waits of the requests still due a first token."""

    def __init__(self, directory: str):
        self.dir = directory
        self._span = None
        self.stop_s = 0.0  # seconds that stopping the profiler took

    def start(self) -> None:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN)
        self._span.__enter__()

    def close_window(self) -> None:
        self._span.__exit__(None, None, None)

    def stop(self) -> None:
        t = time.perf_counter()
        jax.profiler.stop_trace()
        self.stop_s = time.perf_counter() - t


def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: bool, t_start: float = T_START,
             setup_hook=None, control: bool = False) -> dict:
    """One run; returns the result object.  ``setup_hook(eng)``, if given,
    may alter the engine before its warm-up (``bench/faults.py``).  With
    ``control``, the result also holds the lower-precision control's
    verdict on the same sample (``bench/calibrate.py``)."""
    bench, cell, cj, mix, arch = load_cell(root, workload)
    devices = jax.devices()
    dev = devices[0]
    pk = peaks.peaks_for(dev.device_kind) if dev.platform == "tpu" else None

    eng = adapter.make_engine(cj, seed, arch)
    tr = traffic.make_traffic(mix, seconds, seed,
                              eng.model.cfg.vocab_size)
    if setup_hook is not None:
        setup_hook(eng)
    adapter.warm_up(eng)
    compiles = CompileCounter()
    tmp = None
    tracer = None
    if trace:
        tmp = tempfile.mkdtemp(prefix="bench_trace_")
        tracer = Tracer(tmp)
    n_open = []

    def on_open():
        if tracer:
            tracer.start()
        n_open.append(compiles.n)

    loop = adapter.OpenLoop(eng, tr, seconds, on_open=on_open,
                            on_close=tracer.close_window if tracer else None)
    try:
        loop.run()
    finally:
        if tracer and n_open:
            tracer.stop()
    compiled_in_window = compiles.n - n_open[0]
    setup_s = loop.t_open - t_start
    stats = dev.memory_stats() or {}
    memory_peak = int(stats.get("peak_bytes_in_use", 0))

    reduced = None
    if trace:
        data = jax.profiler.ProfileData.from_file(trace_mod.find_xplane(tmp))
        reduced = trace_mod.reduce(data)
        del data
        shutil.rmtree(tmp, ignore_errors=True)

    ctx = Ctx(loop=loop, m=cj["model"], peaks=pk, reduced=reduced,
              setup_s=setup_s, seconds=seconds, drain_every=eng.drain_every,
              arch=arch)
    metrics = {}
    for spec in cell_metrics(bench, cell, trace):
        value = read_metric(root, spec["name"], ctx)
        if value is not None:
            metrics[spec["name"]] = {"value": value, "unit": spec["unit"]}

    print(f"bench: served {loop.t_stop - loop.t_end:.3f} s past the "
          f"window; stopping the profiler took "
          f"{tracer.stop_s if tracer else 0.0:.3f} s; time to first token "
          f"of the window's requests, s: "
          f"{sorted(round(float(x), 3) for x in loop.ttft_s())}",
          file=sys.stderr)
    finished = loop.finished()
    attempted = len(loop.reqs)
    failed = loop.failed
    unanswered = loop.unanswered()
    # free the program's state before the reference allocates
    loop.eng = None
    del eng
    gc.collect()

    got = correct.compare(cj, seed, correct.sample(finished, seed),
                          control=control, arch=arch)
    ok, checks = verdict(got, cj["correct"], compiled_in_window, unanswered)

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell["chips"], "memory_peak_bytes": memory_peak}
    out = {"correct": ok, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced.busy_ns / 1e9
        device["window_s"] = reduced.window_ns / 1e9
        out["breakdown"] = trace_mod.breakdown(reduced)
    if control:
        c_ok, c_checks = verdict(got, cj["correct"], compiled_in_window,
                                 unanswered, "control_")
        out["control"] = {"correct": c_ok, "checks": c_checks,
                          "readings": got}
    out["checks"] = checks
    return out


def verdict(got: dict, lim: dict, compiled_in_window: int, unanswered: int,
            prefix: str = "") -> tuple[bool, dict]:
    """``correct`` and the numbers compared, each with its limit.  With
    ``prefix="control_"``, the control's readings in the program's place."""
    checks = {
        "widest_logit_gap": {"value": got[prefix + "widest_logit_gap"],
                             "limit": lim["max_logit_gap"]},
        "mean_logit_gap": {"value": got[prefix + "mean_logit_gap"],
                           "limit": lim["max_mean_logit_gap"]},
        "tokens_compared": {"value": got["tokens_compared"],
                            "limit": lim["min_tokens_compared"]},
        "compiles_in_window": {"value": compiled_in_window, "limit": 0},
        "unanswered": {"value": unanswered, "limit": 0},
    }
    ok = (None not in (lim["max_logit_gap"], lim["max_mean_logit_gap"])
          and checks["widest_logit_gap"]["value"] <= lim["max_logit_gap"]
          and checks["mean_logit_gap"]["value"] <= lim["max_mean_logit_gap"]
          and got["tokens_compared"] >= lim["min_tokens_compared"]
          and compiled_in_window == 0 and unanswered == 0)
    return bool(ok), checks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="run on the CPU at whatever size the cell has: a "
                         "rehearsal for tests, never a measurement")
    args = ap.parse_args(argv)

    _, cell, _, _, _ = load_cell(ROOT, args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" and not args.cpu_rehearsal:
        print(f"bench: JAX found no TPU (device 0 is "
              f"{devices[0].platform!r}); nothing was run", file=sys.stderr)
        return NO_DEVICE
    if len(devices) < cell["chips"]:
        print(f"bench: {args.workload} needs {cell['chips']} chips, JAX "
              f"found {len(devices)}; nothing was run", file=sys.stderr)
        return NO_DEVICE
    if not args.cpu_rehearsal:
        set_compile_cache(ROOT)

    out = run_cell(ROOT, args.workload, args.seed, args.seconds,
                   bool(args.trace))
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
