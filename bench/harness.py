"""The benchmark harness: resolve a cell of ``BENCHMARK.json`` to its files,
run it, and print its result line.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric is found by name:

* ``bench/configs/<config>.json`` — the configuration as it is run, and
  ``bench/configs/<config>.py`` — its plain reference;
* ``bench/traffic/<traffic>.json`` — the mix's parameters; its ``generator``
  names the general generator in ``bench/generators/<generator>.py`` that reads it;
* ``bench/cells/<cell>.json`` — the limits that decide ``correct`` there;
* ``bench/metrics/<metric>.py`` — the reader of one per-layer metric.

A generator's ``run(run)`` sets up the cell, calls ``run.window_start()`` and
``run.window_end()`` around the measured window, frees the program's state,
compares with the reference through ``run.check``, and returns its
end-to-end values.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class NoChip(RuntimeError):
    """The cell cannot run here: no TPU, or fewer chips than it asks for."""


def load_module(path: str):
    """Import a file by path (names such as ``mfu.feed.py`` hold dots)."""
    name = "bench_" + os.path.basename(path)[:-3].replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _read_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    """One entry of ``workloads`` with every file it names, resolved."""

    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]
    traffic_name: str
    traffic: Dict[str, Any]
    limits: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    root: str = ROOT

    def path(self, *parts: str) -> str:
        return os.path.join(self.root, "bench", *parts)

    @property
    def generator_file(self) -> str:
        return self.path("generators", self.traffic["generator"] + ".py")

    @property
    def reference_file(self) -> str:
        return self.path("configs", self.config_name + ".py")

    def metric_file(self, name: str) -> str:
        return self.path("metrics", name + ".py")

    def files(self) -> List[str]:
        """Every file the cell resolves to."""
        return ([self.path("configs", self.config_name + ".json"), self.reference_file,
                 self.path("traffic", self.traffic_name + ".json"), self.generator_file,
                 self.path("cells", self.name + ".json")]
                + [self.metric_file(m["name"]) for m in self.per_layer])


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(name: str, root: str = ROOT) -> Cell:
    bench = _read_json(os.path.join(root, "BENCHMARK.json"))
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have {sorted(work)}")
    w = work[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config_file = os.path.join(root, conf["file"])
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config_name=w["config"],
        config=_read_json(config_file),
        traffic_name=w["traffic"],
        traffic=_read_json(os.path.join(root, "bench", "traffic", w["traffic"] + ".json")),
        limits=_read_json(os.path.join(root, "bench", "cells", name + ".json"))["limits"],
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
        root=root,
    )


@dataclass
class Run:
    """What a generator gets: the cell, the run's arguments, and the hooks
    that time the window, trace it, and record checks and counters."""

    cell: Cell
    seed: int
    seconds: float
    trace: bool
    t_process: float
    workdir: str
    counters: Dict[str, float] = field(default_factory=dict)
    checks: List[tuple] = field(default_factory=list)
    attempted: int = 0
    failed: int = 0
    t_window: Optional[float] = None
    window_s: Optional[float] = None
    memory_peak_bytes: Optional[int] = None
    window_compiles: int = 0
    trace_dir: Optional[str] = None
    spans: List[tuple] = field(default_factory=list)  # (name, start_ns, end_ns) on the trace's clock
    compiles: int = 0  # compile requests that missed jit's in-memory cache
    _trace_t0: int = 0

    # ---- the reference ----------------------------------------------------
    def reference(self):
        return load_module(self.cell.reference_file)

    # ---- spans -------------------------------------------------------------
    def span(self, name: str):
        """A host span in a traced run, kept by the harness on the trace's
        clock (nanoseconds since the trace started, to about a millisecond)."""
        return self._span(name) if self.trace else contextlib.nullcontext()

    @contextlib.contextmanager
    def _span(self, name: str):
        t0 = time.perf_counter_ns()
        try:
            yield
        finally:
            self.spans.append((name, t0 - self._trace_t0, time.perf_counter_ns() - self._trace_t0))

    # ---- window ------------------------------------------------------------
    def window_start(self) -> None:
        if self.trace:
            import jax

            self.trace_dir = os.path.join(self.workdir, "trace")
            # device operations only: the host tracer, at any level, slows the
            # feed's host-to-device copies some twentyfold (on a v5e), so the
            # benchmark keeps its own host spans
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 0
            self._trace_t0 = time.perf_counter_ns()
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self._window_t0 = time.perf_counter_ns() - self._trace_t0
        self._compiles_at_start = self.compiles
        self.counters["host_rss_peak_setup_bytes"] = host_rss_peak()
        self.t_window = time.perf_counter()

    def window_end(self) -> None:
        """Close the window: its length, compilations in it, the device's
        peak memory; stop the trace."""
        self.window_s = time.perf_counter() - self.t_window
        self.window_compiles = self.compiles - self._compiles_at_start
        if self.trace:
            import jax

            self.spans.append(("bench.window", self._window_t0, time.perf_counter_ns() - self._trace_t0))
            jax.profiler.stop_trace()
        self.memory_peak_bytes = peak_bytes(self.cell.chips)

    @property
    def setup_s(self) -> float:
        return self.t_window - self.t_process

    # ---- correctness -------------------------------------------------------
    def check(self, name: str, value: float) -> None:
        """Record one compared number against its limit in the cell's file."""
        self.checks.append((name, float(value), float(self.cell.limits[name])))

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(
            math.isfinite(v) and v <= lim for _, v, lim in self.checks)


def host_rss_peak() -> int:
    """This process's peak resident host memory so far, in bytes."""
    import resource

    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024


def peak_bytes(chips: int) -> Optional[int]:
    """Peak bytes in use on the fullest of the first ``chips`` devices."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in jax.devices()[:chips]]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def require_chips(chips: int) -> None:
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU, found {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"needs {chips} chips, found {len(devs)}")


def _device_info(run: Run, summary) -> Dict[str, Any]:
    import jax

    devs = jax.devices()[: run.cell.chips]
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs),
            "memory_peak_bytes": run.memory_peak_bytes}
    if summary is not None:
        info["busy_s"] = summary.busy_s
        info["window_s"] = summary.window_s
    return info


def execute(argv: List[str], *, require_tpu: bool = True,
            t_process: Optional[float] = None, root: str = ROOT) -> Dict[str, Any]:
    """Run one cell; return the result line as a dict."""
    import argparse

    p = argparse.ArgumentParser(description="Run one benchmark cell.")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    t_process = time.perf_counter() if t_process is None else t_process

    cell = resolve(args.workload, root)
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    print(f"[bench] compile cache: {enable_compile_cache()}", file=sys.stderr)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if require_tpu:
        require_chips(cell.chips)
    workdir = os.path.join(root, "experiments", "bench", cell.name)
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    run = Run(cell=cell, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
              t_process=t_process, workdir=workdir)

    def count_compiles(event: str, **kw) -> None:
        # a compilation, or a load from the persistent cache
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            run.compiles += 1

    jax.monitoring.register_event_listener(count_compiles)
    try:
        e2e = load_module(cell.generator_file).run(run)
        summary = None
        if run.trace:
            from bench import trace

            kernels = {k: v["match"] for k, v in cell.traffic.get("kernels", {}).items()}
            summary = trace.reduce(run.trace_dir, run.spans, kernels=kernels)
        e2e["setup_s"] = run.setup_s
        if run.trace:
            metrics = {}
            for m in cell.per_layer:
                value = load_module(cell.metric_file(m["name"])).read(
                    run=run, summary=summary, e2e=e2e)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        else:
            metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                       for m in cell.end_to_end}
        line: Dict[str, Any] = {
            "correct": run.correct,
            "attempted": run.attempted,
            "failed": run.failed,
            "metrics": metrics,
            "device": _device_info(run, summary),
        }
        if summary is not None:
            line["breakdown"] = summary.breakdown()
        line["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in run.checks}
        run.counters["host_rss_peak_bytes"] = host_rss_peak()
        print(f"[bench] window_s={run.window_s} window_compiles={run.window_compiles} "
              f"counters={json.dumps(run.counters)}", file=sys.stderr)
        for n, v, lim in run.checks:
            print(f"check {n} value={v} limit={lim}", file=sys.stderr)
        sys.stderr.flush()
        return line
    finally:
        jax.monitoring.unregister_event_listener(count_compiles)
        shutil.rmtree(workdir, ignore_errors=True)


def main(argv: Optional[List[str]] = None, *, t_process: Optional[float] = None) -> int:
    try:
        line = execute(sys.argv[1:] if argv is None else argv, t_process=t_process)
    except NoChip as e:
        print(f"bench: {e}; not running", file=sys.stderr)
        return 3
    print(json.dumps(line), flush=True)
    return 0

