"""Run one benchmark cell once and print its result as the last line.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is found by name from ``BENCHMARK.json``: the
workload names a configuration (``perfbench/configs/<config>.json``) and a
traffic mix (``perfbench/traffic/<traffic>.json``); the traffic names its
loop (``perfbench/loops/<loop>.py``); the comparison's limits are in
``perfbench/limits/<workload>.json``; each per-layer metric is read by
``perfbench/metrics/<metric>.py``.  A new cell, configuration, traffic mix
or metric is new files and entries, never an edit here.

A loop provides ``setup(config, traffic, seed, spans) -> state``
(everything before the window, warm-up included), ``window(state, seconds,
spans, poll) -> dict`` (``attempted``, ``failed``, ``counters`` and the
``work_per_round`` of ``perfbench/lib/work.py``; it calls ``poll()``
between units of work), ``end_to_end(state, spans)`` (its end-to-end
metrics), ``release(state)`` (frees the program's device state) and
``check(state)`` (the compared numbers, by the names of the limits file).

The run needs an accelerator: with none, or fewer chips than the cell asks
for, it exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".perfbench_trace")
# a traced run profiles the units of work that start in this much of its
# window (one w8a solve): on a v5e host, stopping the profiler after two
# w8a solves took about 140 s, and a traced run has to end within 360 s
TRACE_SECONDS = 5.0

if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


def load_module(path: str, name: str):
    if not os.path.isfile(path):
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    loop: object
    end_to_end: list  # metric entries of BENCHMARK.json this cell reports
    per_layer: list
    readers: dict  # per-layer metric name -> its reader module


def _applies(metric: dict, workload: str, reported_e2e: set) -> bool:
    if "workloads" in metric:
        return workload in metric["workloads"]
    return metric.get("moves", metric["name"]) in reported_e2e


def resolve(bench: dict, workload: str, root: str = ROOT) -> Cell:
    """Find everything a cell needs by the names ``bench`` gives, in the
    checkout at ``root``."""
    bench_dir = os.path.join(root, "perfbench")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _read_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = _read_json(os.path.join(bench_dir, "traffic", w["traffic"] + ".json"))
    limits = _read_json(os.path.join(bench_dir, "limits", workload + ".json"))
    loop = load_module(
        os.path.join(bench_dir, "loops", traffic["loop"] + ".py"),
        "perfbench_loop_" + traffic["loop"],
    )
    e2e = [m for m in bench["end_to_end"] if "workloads" not in m or workload in m["workloads"]]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"] if _applies(m, workload, names)]
    readers = {
        m["name"]: load_module(
            os.path.join(bench_dir, "metrics", m["name"] + ".py"),
            "perfbench_metric_" + m["name"].replace(".", "_"),
        )
        for m in layer
    }
    return Cell(workload, w["chips"], config, traffic, limits, loop, e2e, layer, readers)


class Spans:
    """The benchmark's own host spans: wall seconds per name, each also a
    ``bench.<name>`` annotation in the profiler's trace when one runs."""

    def __init__(self):
        self._s: dict[str, list[float]] = {}

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench." + name):
            yield
        self._s.setdefault(name, []).append(time.perf_counter() - t0)

    def seconds(self, name: str) -> list[float]:
        return list(self._s.get(name, ()))


class CompileCounter:
    """Compilations and persistent-cache hits seen while it is armed."""

    def __init__(self):
        from jax import monitoring

        self.armed = False
        self.compiles = 0
        self.cache_hits = 0

        def on_event(event, **kw):
            if self.armed and event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        def on_duration(event, duration, **kw):
            if self.armed and event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1

        monitoring.register_event_listener(on_event)
        monitoring.register_event_duration_secs_listener(on_duration)

    @property
    def fresh_compiles(self) -> int:
        return self.compiles - self.cache_hits


class Tracer:
    """The profiler over the first ``seconds`` of the window: started with
    the window, stopped at the first ``poll`` (the loop calls it between
    units of work) after ``seconds``, or with the window.  The traced part
    is the ``bench.window`` annotation the trace reducer reads."""

    def __init__(self, on: bool, seconds: float):
        self.on, self.seconds = on, seconds
        self._ann = None

    def start(self) -> None:
        if not self.on:
            return
        import jax

        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        # device ops and the benchmark's annotations only: no Python call
        # tracing (it would slow every open) and no HLO protos in the file
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
        self._ann = jax.profiler.TraceAnnotation("bench.window")
        self._ann.__enter__()
        self._t0 = time.perf_counter()

    def poll(self) -> None:
        if self._ann is not None and time.perf_counter() - self._t0 >= self.seconds:
            self.stop()

    def stop(self) -> None:
        if self._ann is None:
            return
        import jax

        self._ann.__exit__(None, None, None)
        self._ann = None
        jax.profiler.stop_trace()


@dataclasses.dataclass
class Context:
    """What a per-layer metric reader may read."""

    spans: Spans
    counters: dict
    trace: object  # lib.trace.TraceSummary or None
    peaks: object  # lib.peaks.Peaks
    work_per_round: object  # lib.work.Work or None


def find_devices(chips: int, require_chip: bool):
    import jax

    devices = jax.devices()
    if require_chip:
        if devices[0].platform == "cpu":
            raise NoChip(f"JAX found no accelerator (platform {devices[0].platform!r})")
        if len(devices) < chips:
            raise NoChip(f"the cell needs {chips} chips, JAX found {len(devices)}")
    return devices[:chips]


def configure_cache() -> None:
    """JAX's persistent compilation cache at one fixed path inside the
    checkout, whatever the environment names, with every program cached:
    the program takes a directory that is already set."""
    import jax

    jax.config.update("jax_enable_x64", True)
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def memory_peak(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    peaks = [int(p) for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_chip: bool = True, peaks=None) -> dict:
    """Set up, measure, check and report one run of ``cell``."""
    from perfbench.lib import peaks as peak_table
    from perfbench.lib import trace as trace_lib

    devices = find_devices(cell.chips, require_chip)
    if peaks is None:
        peaks = peak_table.peaks_for(devices[0].device_kind)
    counter = CompileCounter()
    loop = cell.loop
    state = loop.setup(cell.config, cell.traffic, seed, Spans())
    gc.collect()  # what set-up left to collect, collected in set-up
    setup_s = time.perf_counter() - t_start

    spans = Spans()
    tracer = Tracer(trace, TRACE_SECONDS)
    tracer.start()
    counter.armed = True
    win = loop.window(state, seconds, spans, tracer.poll)
    counter.armed = False
    t_trace = time.perf_counter()
    tracer.stop()
    summary = None
    if trace:
        path = trace_lib.find_xplane(TRACE_DIR)
        summary = trace_lib.reduce_file(path) if path else None
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    trace_s = time.perf_counter() - t_trace
    mem = memory_peak(devices)

    metrics = {}
    if trace:
        ctx = Context(spans, win["counters"], summary, peaks, win.get("work_per_round"))
        for m in cell.per_layer:
            value = cell.readers[m["name"]].read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = {"setup_s": setup_s, **loop.end_to_end(state, spans)}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}

    loop.release(state)
    t_check = time.perf_counter()
    numbers = loop.check(state)
    check_s = time.perf_counter() - t_check
    checks = {}
    for name, limit in cell.limits.items():
        checks[name] = {"value": numbers[name], "limit": limit}
    correct = all(c["value"] <= c["limit"] for c in checks.values()) and win["failed"] == 0

    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": mem,
    }
    result = {
        "correct": bool(correct),
        "attempted": int(win["attempted"]),
        "failed": int(win["failed"]),
        "metrics": metrics,
        "device": device,
    }
    if trace and summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = {"device_ops": summary.top_ops, "idle_gaps": summary.idle_gaps}
    result["diagnostics"] = {
        "setup_s": setup_s,
        "check_s": check_s,
        "trace_s": trace_s,
        "open_s": spans.seconds("open"),
        "run_s": spans.seconds("run"),
        "window_compiles": counter.fresh_compiles,
        "window_cache_hits": counter.cache_hits,
        **{k: v for k, v in win["counters"].items()},
        **({"idle_by_span": summary.idle_by_span} if summary is not None else {}),
    }
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = _read_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = resolve(bench, args.workload)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import repro.api  # noqa: F401  (the system under test; absent, no run)

    configure_cache()
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace), t_start=T_START)
    except NoChip as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 3
    for name, c in result["checks"].items():
        verdict = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {name} {c['value']!r} limit {c['limit']!r} {verdict}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
