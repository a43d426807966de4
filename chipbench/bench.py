"""The harness: finds a cell's files by name, checks the chip, runs the
cell's driver through set-up, the measured window and the check, and
prints the result.

Everything that belongs to one cell is found by name from
``BENCHMARK.json``:

- the workload names a ``config`` and a ``traffic``;
- ``configs/<config>.json`` holds the configuration as it is run, and
  names its ``driver`` (``drivers/<driver>.py``) and its plain
  ``reference`` (a file beside it in ``configs/``);
- ``traffic/<traffic>.json`` holds the mix's parameters, read by
  ``traffic.py``;
- each metric is read by ``metrics/<metric>.py``, whose ``read(run)``
  returns a number or ``None`` when it finds nothing to read; a metric
  split by its cells' end-to-end metric (``device_idle_share.pool``)
  falls back to the reader of the name before its first dot
  (``metrics/device_idle_share.py``) where it has none of its own.

A driver module defines ``Run(config, traffic, seed, seconds, spans)``
with ``setup()``, ``window()``, ``release()``, ``check()`` and
``notes()``; see ``drivers/pool.py``.  A traced run traces its whole
window, which lasts ``Run.trace_seconds`` where that is shorter than
``--seconds``: a trace of the full window would be too large to read
within a run's time, and stopping the profiler part way through would
stall the rest of the window behind it.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys
import tempfile
import time
from contextlib import contextmanager
from typing import List, Optional, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
WINDOW_SPAN = "bench.window"


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(path: str):
    """Import a file of the benchmark by its path (names may hold dots
    and dashes)."""
    name = "chipbench_" + os.path.relpath(path, HERE).replace(
        os.sep, "_").replace(".", "_").replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def cell_files(bench: dict, workload: str) -> Tuple[dict, dict, dict]:
    """``(workload entry, config, traffic)`` of one cell."""
    wl = {w["name"]: w for w in bench["workloads"]}.get(workload)
    if wl is None:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json; "
                         f"known: {[w['name'] for w in bench['workloads']]}")
    cfg = {c["name"]: c for c in bench["configs"]}[wl["config"]]
    config = load_json(ROOT, cfg["file"])
    traffic = load_json(HERE, "traffic", f"{wl['traffic']}.json")
    return wl, config, traffic


def cell_metrics(bench: dict, workload: str, traced: bool) -> List[dict]:
    """The metrics a run of ``workload`` reports: its end-to-end metrics
    untraced, its per-layer metrics traced."""
    def listed(m):
        return "workloads" not in m or workload in m["workloads"]
    e2e = [m for m in bench["end_to_end"] if listed(m)]
    if not traced:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if listed(m) and m["moves"] in names]


def reader(metric: str) -> str:
    """The path of ``metric``'s reader: ``metrics/<metric>.py``, or
    where there is none, ``metrics/<metric up to its first dot>.py``."""
    path = os.path.join(HERE, "metrics", f"{metric}.py")
    if os.path.exists(path):
        return path
    return os.path.join(HERE, "metrics", f"{metric.split('.')[0]}.py")


def require_chip(n_chips: int):
    """Exit non-zero, printing no result, unless JAX's devices are at
    least ``n_chips`` TPUs."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"the benchmark needs a TPU; JAX found "
                         f"{devs[0].platform}")
    if len(devs) < n_chips:
        raise SystemExit(f"the cell needs {n_chips} TPU chips; JAX found "
                         f"{len(devs)}")


def enable_compile_cache() -> None:
    """JAX's persistent cache at a fixed path inside the checkout,
    ``<checkout>/.jax_cache``, whatever the environment says, so that two
    checkouts on one machine share nothing.  Every program is kept,
    however quick its compile."""
    import jax
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class Spans:
    """Host spans from the benchmark's own files: each is a
    ``jax.profiler.TraceAnnotation`` (so a traced run sees it on the
    device trace's clock) and, where ``keep``, an entry
    ``(name, start_ns, end_ns)`` of ``log`` on the host clock."""

    def __init__(self):
        self.log: List[Tuple[str, int, int]] = []

    @contextmanager
    def __call__(self, name: str, keep: bool = True):
        import jax
        with jax.profiler.TraceAnnotation(name):
            t0 = time.perf_counter_ns()
            try:
                yield
            finally:
                if keep:
                    self.log.append((name, t0, time.perf_counter_ns()))

    def durations_ms(self, name: str) -> List[float]:
        return [(e - s) * 1e-6 for n, s, e in self.log if n == name]


class CompileCounter:
    """Counts XLA compiles while ``active``."""

    def __init__(self):
        import jax.monitoring
        self.active = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, _secs, **_kw):
        if self.active and name == "/jax/core/compile/backend_compile_duration":
            self.count += 1


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             bench: Optional[dict] = None, config: Optional[dict] = None,
             traffic: Optional[dict] = None, chip: bool = True,
             t_start: Optional[float] = None) -> Tuple[dict, list]:
    """One run of one cell.  Returns ``(result, checks)``; ``checks``
    lists ``(name, value, limit)``, each correct where value <= limit.

    ``chip=False`` skips the look for a chip (for the CPU tests);
    ``config`` and ``traffic`` override the cell's files."""
    t_start = time.perf_counter() if t_start is None else t_start
    bench = bench or load_json(ROOT, "BENCHMARK.json")
    wl, cfg_file, tr_file = cell_files(bench, workload)
    config, traffic = config or cfg_file, traffic or tr_file
    metrics = cell_metrics(bench, workload, trace)
    if chip:
        require_chip(wl["chips"])
    import jax
    enable_compile_cache()
    from chipbench import costs
    from chipbench.trace import Capture

    driver = load_module(os.path.join(HERE, "drivers",
                                      f"{config['driver']}.py"))
    if trace:
        seconds = min(seconds, driver.Run.trace_seconds)
    spans = Spans()
    run = driver.Run(config, traffic, seed, seconds, spans)
    compiles = CompileCounter()
    run.setup()
    run.setup_s = time.perf_counter() - t_start
    run.trace = None
    compiles.active = True
    if trace:
        with tempfile.TemporaryDirectory() as d:
            with Capture(d, WINDOW_SPAN) as cap:
                run.window()
            run.trace = cap.reduce(run.span_prefixes)
    else:
        with spans(WINDOW_SPAN, keep=False):
            run.window()
    compiles.active = False
    dev = jax.devices()[:wl["chips"]]
    stats = [d.memory_stats() or {} for d in dev]
    peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
    run.peaks = costs.peaks(dev[0].device_kind) if chip else None
    values = {m["name"]: load_module(reader(m["name"])).read(run)
              for m in metrics}
    run.release()
    t_check = time.perf_counter()
    checks = run.check() + [("compiles_in_window", compiles.count, 0)]
    notes = dict(run.notes(), check_s=time.perf_counter() - t_check)
    print("notes " + json.dumps(notes), file=sys.stderr)
    device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
              "count": len(dev), "memory_peak_bytes": peak}
    if run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
    units = {m["name"]: m["unit"] for m in metrics}
    result = {
        "correct": all(v <= lim for _, v, lim in checks),
        "attempted": run.attempted,
        "failed": run.failed,
        "metrics": {k: {"value": v, "unit": units[k]}
                    for k, v in values.items() if v is not None},
        "device": device,
    }
    if run.trace is not None:
        result["breakdown"] = {"device_ops": run.trace.device_ops(),
                               "idle_gaps": run.trace.idle_gaps()}
    result["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in checks}
    return result, checks


def emit(result: dict, checks: list) -> None:
    """The compared numbers, each beside its limit, as the last lines of
    standard error; the result as the last line of standard output."""
    for name, value, limit in checks:
        print(f"check {name} = {value!r} (limit {limit!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
