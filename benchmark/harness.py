"""Run one cell of BENCHMARK.json once and print its result line.

Everything that belongs to one configuration, traffic mix or metric is a
file of its own, found by the name BENCHMARK.json gives it:

  benchmark/configs/<config>.json   the deployment (the `file` of a config)
  benchmark/traffic/<traffic>.json  a mix; its "driver" names the module
  benchmark/drivers/<driver>.py     setup(run), window(run), teardown(run),
                                    check(run) for one kind of traffic
  benchmark/metrics/<metric>.py     read(run) -> number, or None where the
                                    run holds nothing to read

A driver fills `Run.host` with what it measured on the host clock and adds
the comparisons that decide `correct` with `Run.check`. The harness takes
the counters, spans, compile events and, with --trace 1, the profiler
trace around the driver's window.
"""

from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import logging
import os
import shutil
import sys
import threading
import time
from dataclasses import dataclass, field

import cluster
import devtrace

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class BenchFailure(RuntimeError):
    """The run cannot stand as a measurement: exit non-zero, no metrics."""


def _load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise BenchFailure(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    spec: dict          # the whole BENCHMARK.json
    workload: dict
    config: dict        # the configuration file's contents
    traffic: dict
    driver: object
    end_to_end: list
    per_layer: list
    root: str = ROOT


def _applies(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = os.path.join(root, "benchmark")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    try:
        wl = next(w for w in spec["workloads"] if w["name"] == name)
    except StopIteration:
        raise BenchFailure(f"no workload {name!r} in BENCHMARK.json") from None
    entry = next(c for c in spec["configs"] if c["name"] == wl["config"])
    with open(os.path.join(root, entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(bench, "traffic", wl["traffic"] + ".json")) as f:
        traffic = json.load(f)
    driver = _load_module(
        os.path.join(bench, "drivers", traffic["driver"] + ".py"),
        "bench_driver_" + traffic["driver"])
    e2e = [m for m in spec["end_to_end"] if _applies(m, name)]
    e2e_names = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if m["moves"] in e2e_names and _applies(m, name)]
    return Cell(name, spec, wl, config, traffic, driver, e2e, layer, root)


@dataclass
class Run:
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    workdir: str
    say: object = print
    chips: int = 1
    host: dict = field(default_factory=dict)
    checks: list = field(default_factory=list)
    attempted: int = 0
    failed: int = 0
    state: dict = field(default_factory=dict)
    # filled by the harness
    setup_s: float = 0.0
    window: tuple = (0.0, 0.0)
    counters0: dict = field(default_factory=dict)
    counters_before: dict = field(default_factory=dict)
    counters_after: dict = field(default_factory=dict)
    spans: list = field(default_factory=list)
    timeline: object = None
    devices: list = field(default_factory=list)
    peaks: dict = field(default_factory=dict)
    compiles_in_window: int = 0
    lowered_in_window: list = field(default_factory=list)
    gc_pauses: list = field(default_factory=list)  # (generation, seconds)
    freezes: list = field(default_factory=list)  # (perf_counter, seconds)
    cpu_s: float = 0.0  # this process's CPU seconds in the window
    memory_peak_bytes: int = 0

    @property
    def config(self) -> dict:
        return self.cell.config

    @property
    def traffic(self) -> dict:
        return self.cell.traffic

    def check(self, name: str, value: float, limit: float) -> None:
        """One comparison that decides `correct`: it holds while value <=
        limit."""
        self.checks.append((name, value, limit))

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(v <= lim for _, v, lim in
                                         self.checks)

    def delta(self, name: str, **labels) -> float:
        return (cluster.total(self.counters_after, name, **labels)
                - cluster.total(self.counters_before, name, **labels))

    def delta_split(self, name: str, label: str, **labels) -> dict:
        a = cluster.split(self.counters_after, name, label, **labels)
        b = cluster.split(self.counters_before, name, label, **labels)
        return {k: v - b.get(k, 0.0) for k, v in a.items()
                if v - b.get(k, 0.0)}

    def span_ms(self, name: str) -> list[float]:
        return [d for n, _, d in self.spans if n == name]

    def annotate(self, name: str):
        """A host span in the profiler's trace (a no-op untraced)."""
        if not self.trace:
            return _NULL
        import jax

        return jax.profiler.TraceAnnotation("bench:" + name)


class _Null:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _Null()


def _batches(snapshot: dict, label: str) -> dict:
    return {k: int(v) for k, v in
            cluster.batches(snapshot, label).items()}


class _Freezes:
    """Times this process could not run for `limit` seconds or more while
    a thread slept `tick` seconds: the whole machine stalled, or one
    thread held the interpreter lock. [(perf_counter, seconds late)]."""

    def __init__(self, tick: float = 0.01, limit: float = 0.05):
        self.tick, self.limit = tick, limit
        self.seen: list = []
        self._stop = threading.Event()
        self._t = threading.Thread(target=self._run, daemon=True)
        self._t.start()

    def _run(self) -> None:
        while True:
            t = time.perf_counter()
            if self._stop.wait(self.tick):
                return
            late = time.perf_counter() - t - self.tick
            if late >= self.limit:
                self.seen.append((t, late))

    def stop(self) -> list:
        self._stop.set()
        self._t.join()
        return self.seen


def _cpu_now() -> float:
    """This process's CPU seconds, user and system."""
    t = os.times()
    return t.user + t.system


def _filesystem(path: str) -> str:
    """'<type> at <mount point>' of the mount that holds `path`."""
    best = ("?", "")
    try:
        with open("/proc/mounts") as f:
            for line in f:
                _, mnt, fs = line.split()[:3]
                if (path == mnt or path.startswith(mnt.rstrip("/") + "/")) \
                        and len(mnt) > len(best[1]):
                    best = (fs, mnt)
    except OSError:
        pass
    return f"{best[0]} at {best[1] or '?'}"


class _Lowered(logging.Handler):
    """(perf_counter, first line) of JAX's "Compiling ..." records."""

    def __init__(self):
        super().__init__(logging.DEBUG)
        self.seen: list = []

    def emit(self, record: logging.LogRecord) -> None:
        msg = record.getMessage()
        if msg.startswith("Compiling "):
            self.seen.append((time.perf_counter(),
                              msg.splitlines()[0][:240]))


class _GcClock:
    """Collector pauses of this process (the servers' process), as
    (generation, seconds), while registered."""

    def __init__(self):
        self.t = None
        self.pauses: list = []

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self.t = time.perf_counter()
        elif self.t is not None:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self.t))
            self.t = None


def execute(cell: Cell, seed: int, seconds: float, trace: bool,
            workdir: str, *, chips: int = 1, devices=None, peaks=None,
            say=print, t_start: float | None = None,
            compile_count=lambda: 0, after_setup=None) -> Run:
    """Set-up, window and check of one run. Needs no chip itself: `main`
    makes the chip checks, tests call this on the CPU. `after_setup(run)`
    runs between set-up and window: the controls and the fault tests put
    their broken path in there."""
    import jax

    t_start = time.perf_counter() if t_start is None else t_start
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    run = Run(cell, seed, seconds, trace, workdir, say=say, chips=chips,
              devices=list(devices or []), peaks=peaks or {})
    run.counters0 = cluster.counters()
    gc_clock = _GcClock()
    trace_dir = os.path.join(BENCH, "out", "trace")
    try:
        cell.driver.setup(run)
        if after_setup is not None:
            after_setup(run)
        run.setup_s = time.perf_counter() - t_start
        run.counters_before = cluster.counters()
        c0 = compile_count()
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            # host annotations and device ops; no Python function tracer,
            # which would slow every host thread of the server
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        gc.callbacks.append(gc_clock)
        jax.config.update("jax_log_compiles", True)
        freezes = _Freezes()
        cpu0 = _cpu_now()
        t0 = time.perf_counter()
        try:
            with run.annotate("window"):
                cell.driver.window(run)
        finally:
            t1 = time.perf_counter()
            run.cpu_s = _cpu_now() - cpu0
            gc.callbacks.remove(gc_clock)
            run.freezes = freezes.stop()
            jax.config.update("jax_log_compiles", False)
            if trace:
                jax.profiler.stop_trace()
        run.gc_pauses = gc_clock.pauses
        run.window = (t0, t1)
        run.compiles_in_window = compile_count() - c0
        run.counters_after = cluster.counters()
        run.spans = cluster.spans_since(t0, t1)
        run.memory_peak_bytes = max(
            [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in run.devices] or [0])
    finally:
        cell.driver.teardown(run)
    cell.driver.check(run)
    if trace:
        run.timeline = devtrace.Timeline.from_dir(trace_dir)
    shutil.rmtree(workdir, ignore_errors=True)
    return run


def fail_closed(run: Run) -> None:
    """Refuse a run that did not measure what the cell says it does."""
    from seaweedfs_tpu.ops import dispatch

    reasons = _batches(cluster.delta(run.counters_after, run.counters0),
                       "reason")
    host = sorted(set(reasons) & dispatch.HOST_REASONS)
    if host:
        raise BenchFailure(f"EC batches ran on a host coder: {host}")
    window = cluster.delta(run.counters_after, run.counters_before)
    lanes = _batches(window, "lane")
    req = run.traffic.get("require", [])
    if "reconstruct_batches" in req and not lanes.get("reconstruct"):
        raise BenchFailure("the degraded window dispatched no reconstruct")
    if "encode_on_every_chip" in req:
        per_chip = cluster.split(window, "SeaweedFS_ec_dispatch_batches",
                                 "chip", lane="encode")
        idle = [c for c in range(run.chips) if not per_chip.get(str(c))]
        if idle:
            raise BenchFailure(f"chips {idle} ran no encode batch "
                               f"({per_chip})")


def metrics(run: Run, specs: list) -> dict:
    out = {}
    for m in specs:
        mod = _load_module(os.path.join(run.cell.root, "benchmark",
                                        "metrics", m["name"] + ".py"),
                           "bench_metric_" + m["name"].replace(".", "_"))
        value = mod.read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def report(run: Run, device: dict, emit=print) -> dict:
    """The lines before the last one, and the result object."""
    window = cluster.delta(run.counters_after, run.counters_before)
    whole = cluster.delta(run.counters_after, run.counters0)
    emit(f"device: platform={device['platform']} kind={device['kind']} "
         f"count={device['count']}")
    emit(f"setup: {run.setup_s:.3f} s; window {run.window[1] - run.window[0]:.3f}"
         f" s; backend compiles in the window: {run.compiles_in_window}")
    for name in run.lowered_in_window[:10]:
        emit(f"lowered and compiled in the window: {name}")
    for label in ("reason", "lane", "chip"):
        emit(f"ec batches by {label}: window {json.dumps(_batches(window, label))}"
             f"; whole run {json.dumps(_batches(whole, label))}")
    for line in run.host.get("lines", []):
        emit(line)
    gcs = []
    for gen in (0, 1, 2):
        p = [s for g, s in run.gc_pauses if g == gen]
        if p:
            gcs.append(f"gen{gen} {len(p)} x, {1e3 * sum(p):.1f} ms in "
                       f"all, longest {1e3 * max(p):.1f} ms")
    emit("collector pauses of the servers' process in the window: "
         + ("; ".join(gcs) or "none"))
    emit(f"freezes of 50 ms or more in the window (at s: ms): "
         + (", ".join(f"{a - run.window[0]:.2f}: {1e3 * d:.0f}"
                      for a, d in run.freezes) or "none"))
    # with the work fixed, a run-to-run shift in a host-bound latency
    # goes with the CPU the servers spent on it
    emit(f"CPU of the servers' process in the window: {run.cpu_s:.2f} s")
    emit(f"work directory on {_filesystem(run.workdir)}")
    result = {
        "correct": run.correct,
        "attempted": run.attempted,
        "failed": run.failed,
        "metrics": metrics(run, run.cell.per_layer if run.trace
                           else run.cell.end_to_end),
        "device": dict(device, memory_peak_bytes=int(run.memory_peak_bytes)),
    }
    if run.trace and run.timeline is not None:
        chips = list(range(run.chips))
        tl = run.timeline
        span = tl.spans("window")
        busy = tl.busy_in("window", chips)
        result["device"]["busy_s"] = sum(busy) / len(busy)
        result["device"]["window_s"] = sum(b - a for a, b in span)
        result["breakdown"] = {"device_ops": tl.top_ops("window", chips),
                               "idle_gaps": tl.idle_gaps("window", chips)}
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, v, lim in run.checks}
    return result


def _limit_to_one_chip() -> None:
    """Make libtpu open one chip only. The one-chip machine describes
    itself as a 2x2 host exposing one chip; on a host that exposes four,
    a one-chip cell must not take (or report) the other three."""
    os.environ.setdefault("TPU_VISIBLE_CHIPS", "0")
    os.environ.setdefault("TPU_CHIPS_PER_PROCESS_BOUNDS", "1,1,1")
    os.environ.setdefault("TPU_PROCESS_BOUNDS", "1,1,1")


def peaks_for(kind: str) -> dict:
    with open(os.path.join(BENCH, "peaks.json")) as f:
        table = json.load(f)
    if kind not in table["devices"]:
        raise BenchFailure(f"device kind {kind!r} is not in "
                           f"benchmark/peaks.json")
    return table["devices"][kind]


def open_chips(chips: int) -> tuple[list, dict, dict]:
    """Take the chips a cell asks for, with the compile cache in the
    checkout: (the cell's devices, the device as JAX reports it, its
    peaks). Raises BenchFailure on another platform or too few chips.
    The benchmark's runs and the controls both enter here."""
    if chips == 1:
        _limit_to_one_chip()
    # the compile cache lives in the checkout, at a fixed path
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(BENCH, ".jax_cache")
    sys.path.insert(0, ROOT)
    from seaweedfs_tpu.parallel import mesh

    mesh.enable_compile_cache()
    devices = mesh.local_devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != "tpu":
        raise BenchFailure(f"found platform {device['platform']!r}, "
                           f"not a TPU")
    if len(devices) < chips:
        raise BenchFailure(f"{len(devices)} devices visible, the cell "
                           f"needs {chips}")
    return devices[:chips], device, peaks_for(device["kind"])


def main(argv=None, t_start: float | None = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    p = argparse.ArgumentParser(description="Run one benchmark cell once.")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    def err(line: str) -> None:
        print(line, file=sys.stderr, flush=True)

    try:
        cell = load_cell(args.workload)
        chips = int(cell.workload["chips"])
        if args.trace:
            # room for every span of the traced window in the program's
            # ring; untraced runs keep the program's default
            os.environ.setdefault("SWFS_TRACE_BUF", str(1 << 20))
        devices, device, peaks = open_chips(chips)
        import jax

        compiles = [0]

        def on_duration(event, duration, **_):
            if event == COMPILE_EVENT:
                compiles[0] += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        # name what compiles in the window: JAX logs each program it
        # lowers and compiles (a persistent-cache load included) while
        # jax_log_compiles is on, which `execute` turns on for the window
        lowered = _Lowered()
        logging.getLogger("jax").addHandler(lowered)
        run = execute(cell, args.seed, args.seconds, bool(args.trace),
                      os.path.join(BENCH, "out", "work"), chips=chips,
                      devices=devices, peaks=peaks, say=err,
                      t_start=t_start, compile_count=lambda: compiles[0])
        a, b = run.window
        run.lowered_in_window = [m for t, m in lowered.seen if a <= t <= b]
        fail_closed(run)
        result = report(run, device, emit=err)
    except BenchFailure as e:
        err(f"FAIL: {e}")
        return 1
    for name, v, lim in run.checks:
        err(f"check {name}: {v} (limit {lim})")
    print(json.dumps(result), flush=True)
    return 0
