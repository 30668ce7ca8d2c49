#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name from ``BENCHMARK.json`` at the root of the
checkout: the cell's configuration (``bench/configs/<config>.json`` with
its generator ``<config>.py`` beside it), its traffic mix
(``bench/mixes/<traffic>.json``, whose ``loop`` names a general loop
in ``bench/loops/``) and each metric's reader
(``bench/metrics/<metric>.py``).  A new cell, configuration, mix or
metric is new files and new entries; no file here changes.

A run makes its data from ``--seed``, builds and warms the program under
test (``src/repro``), then measures for ``--seconds``.  With ``--trace
1`` the window runs under the JAX profiler and the cell's per-layer
metrics are printed in place of its end-to-end metrics.  Once the window
has closed and the peak memory is read, the program's state is freed and
its answers are compared with the plain reference (``reference.py``).
Each compared number is printed beside its limit as the last lines of
standard error, and under ``checks``, the last key of the result line,
which is the last line of standard output.

Without a TPU, or with fewer chips than the cell asks for, it exits 2
and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)


class NoChip(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


def load_module(path: str):
    spec = importlib.util.spec_from_file_location(
        os.path.basename(path)[:-3].replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Bench:
    """``BENCHMARK.json`` and the files it names, under ``root``."""

    def __init__(self, root: str):
        self.root = root
        self.here = os.path.join(root, "bench")
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            self.spec = json.load(f)
        sys.path.insert(0, os.path.join(self.here, "metrics"))

    def cell(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str):
        entry = next(c for c in self.spec["configs"] if c["name"] == name)
        path = os.path.join(self.root, entry["file"])
        with open(path) as f:
            cfg = json.load(f)
        return cfg, load_module(path[:-len(".json")] + ".py")

    def mix(self, name: str):
        with open(os.path.join(self.here, "mixes", name + ".json")) as f:
            mix = json.load(f)
        return mix, load_module(os.path.join(self.here, "loops",
                                             mix["loop"] + ".py"))

    def metrics(self, cell: dict, traced: bool) -> list:
        """The cell's end-to-end metrics, or with ``traced`` the per-layer
        metrics that list the cell."""
        if traced:
            return [m for m in self.spec["per_layer"]
                    if cell["name"] in m["workloads"]]
        return [m for m in self.spec["end_to_end"]
                if cell["name"] in m.get("workloads", [cell["name"]])]

    def reader(self, metric: str):
        return load_module(os.path.join(self.here, "metrics", metric + ".py"))


class CompileCounter:
    """Compile requests, their seconds and persistent-cache hits, from
    JAX's own monitoring events.  A request served from the persistent
    cache still emits ``backend_compile_duration`` (the load), so the
    seconds are compile plus cache-load time."""

    def __init__(self, jax):
        self.seconds, self.compiles, self.cache_hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event.endswith("backend_compile_duration"):
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event.endswith("cache_hits"):
            self.cache_hits += 1


def start_jax(require_tpu: bool, chips: int):
    """Import JAX with the compile cache inside the checkout (unless
    ``JAX_COMPILATION_CACHE_DIR`` names one) and check the devices."""
    sys.path.insert(0, os.path.join(ROOT, "src"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no logs in /tmp
    import jax
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX sees {devices[0].platform} devices")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees "
                     f"{len(devices)}")
    import repro  # noqa: F401  (the program under test)
    return jax, devices


def span_factory(traced: bool, jax):
    """``span(name)``: a profiler span ``bench.<name>`` in a traced run,
    nothing otherwise."""
    if not traced:
        return lambda name: contextlib.nullcontext()
    return lambda name: jax.profiler.TraceAnnotation("bench." + name)


def run(args, *, root: str = ROOT, require_tpu: bool = True) -> dict:
    """One run of one cell; returns the result line as a dict.  The
    checks' lines go to standard error as they are made."""
    bench = Bench(root)
    cell = bench.cell(args.workload)
    cfg, cfgmod = bench.config(cell["config"])
    mix, loopmod = bench.mix(cell["traffic"])
    chips = int(cell["chips"])
    jax, devices = start_jax(require_tpu, chips)
    kind = devices[0].device_kind
    with open(os.path.join(bench.here, "peaks.json")) as f:
        peaks = json.load(f)
    if require_tpu and kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    clock = CompileCounter(jax)
    traced = bool(args.trace)
    span = span_factory(traced, jax)

    t0 = time.perf_counter()
    data = cfgmod.make(cfg, args.seed)
    t1 = time.perf_counter()
    loop = loopmod.Loop(cfg, cfgmod, data, mix, args.seed, span)
    loop.setup(args.seconds)
    phases = f"start={t0 - T_START} data={t1 - t0} " \
        f"build_and_warm={time.perf_counter() - t1}"
    trace_dir = os.path.join(bench.here, "out", "trace", cell["name"])
    if traced:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
    c0 = (clock.compiles, clock.cache_hits)
    setup_s = time.perf_counter() - T_START
    setup_compile_s = clock.seconds
    with span("window"):
        raw = loop.window(args.seconds)
    if traced:
        jax.profiler.stop_trace()
    window_compiles = clock.compiles - c0[0]
    window_cache_loads = clock.cache_hits - c0[1]
    used = devices[:chips]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in used)
    rows = loop.rows_stored()
    loop.release()

    summary = None
    if traced:
        import trace_reduce
        recorded = trace_reduce.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        if recorded.ops:
            summary = trace_reduce.reduce(recorded, chips)
        elif require_tpu:                    # a CPU has no device plane
            raise RuntimeError("the trace holds no device operations")
    ctx = {"cell": cell, "cfg": cfg, "mix": mix, "raw": raw,
           "setup_s": setup_s, "peak_bytes": peak, "rows_stored": rows,
           "trace": summary, "peaks": peaks.get(kind)}
    metrics = {}
    for m in bench.metrics(cell, traced):
        value = bench.reader(m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    late = raw.get("late_s")
    log = [f"setup: seconds={setup_s} {phases} compile_seconds="
           f"{setup_compile_s} compile_requests={c0[0]} cache_loads={c0[1]}",
           f"window: seconds={raw['window_s']} compile_requests="
           f"{window_compiles} cache_loads={window_cache_loads} "
           f"peak_bytes_in_use={peak} rows_stored={rows}"]
    if late is not None and late.size:
        log.append(f"generator: late_p50_ms={np.percentile(late, 50) * 1e3} "
                   f"late_p99_ms={np.percentile(late, 99) * 1e3} "
                   f"late_max_ms={late.max() * 1e3}")
    log.append("counters: " + " ".join(f"{k}={v}" for k, v in
                                       raw["counters"].items()))
    if raw.get("diagnostics"):
        log.append("diagnostics: " + " ".join(
            f"{k}={v}" for k, v in raw["diagnostics"].items()))
    for k, v in raw.items():               # every latency sample's tail
        if k.endswith("_latency_s") and len(v):
            ms = np.percentile(v, [50, 90, 95, 99, 100]) * 1e3
            log.append(f"{k[:-2]}_ms: n={len(v)} " + " ".join(
                f"p{q}={x}" for q, x in zip((50, 90, 95, 99, 100), ms)))
    for line in log:
        print(line, file=sys.stderr, flush=True)

    checks = loop.check()
    for name, value, limit in checks:
        print(f"check: {name}={value} limit={limit}", file=sys.stderr,
              flush=True)
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": peak}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
    result = {"correct": all(v <= lim for _, v, lim in checks),
              "attempted": raw["attempted"], "failed": raw["failed"],
              "metrics": metrics, "device": device}
    if summary is not None:
        result["breakdown"] = summary.breakdown()
    result["checks"] = {n: {"value": v, "limit": lim}
                        for n, v, lim in checks}
    return result


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    try:
        result = run(args)
    except NoChip as e:
        print(e, file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
