"""One run of one cell: set-up, the measured window, the check, the result.

Everything a cell needs is found by name from ``BENCHMARK.json``: the
configuration's file, ``bench/traffic/<traffic>.json``, and one reader per
metric, ``bench/end_to_end/<metric>.py`` and ``bench/layer_metrics/<metric>.py``,
each with ``read(run) -> float | None``. A reader that finds nothing to read
returns None and the metric is left out of the result.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import shutil
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".bench_cache" / "jax"
TRACE_DIR = ROOT / ".bench_cache" / "trace"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


class NoDevice(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# -- what BENCHMARK.json names ------------------------------------------------

def load_spec(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def resolve(spec: dict, workload: str, root: Path = ROOT) -> dict:
    """The cell ``workload`` with its configuration, traffic and metrics."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    cell = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    entry = configs[cell["config"]]
    config = json.loads((root / entry["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())

    def mine(metric):
        return workload in metric.get("workloads", [workload])

    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": [m for m in spec["end_to_end"] if mine(m)],
            "per_layer": [m for m in spec["per_layer"] if mine(m)]}


def reader(kind: str, name: str, root: Path = ROOT):
    """The ``read`` function of ``bench/<kind>/<name>.py``."""
    path = root / "bench" / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no reader for metric {name!r} at {path}")
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


# -- what a run measured --------------------------------------------------------

@dataclasses.dataclass
class Solve:
    seconds: float
    rounds: int
    records: int
    stop_round: int | None
    certified: bool
    error: str | None = None
    x: object = dataclasses.field(default=None, repr=False)


@dataclasses.dataclass
class Run:
    """What the readers read."""
    cell: dict
    config: dict
    traffic: dict
    chips: int
    device_kind: str
    setup_s: float = 0.0
    window_s: float = 0.0
    solves: list = dataclasses.field(default_factory=list)
    peak_bytes: list = dataclasses.field(default_factory=list)
    # (phase, event, seconds): compilations and lowerings, by phase
    compiles: list = dataclasses.field(default_factory=list)
    trace: object = None   # trace_reduce.Summary of the traced window

    def ok(self) -> list:
        return [s for s in self.solves if s.error is None and (
            s.certified or self.traffic["kind"] != "certified_solves")]

    def shape(self):
        from bench import counts
        return counts.Shape.of(self.config)

    def window_cost(self):
        """Operations and bytes of the rounds of the window's solves."""
        from bench import counts
        recorder = self.traffic["recorder"]
        total = counts.Cost()
        for s in self.solves:
            total = total + counts.window_rounds(self.shape(), s.rounds,
                                                 s.records, recorder)
        return total


class CompileLog:
    """JAX's compilation and lowering events, each tagged with the phase in
    which it came."""

    def __init__(self, run: Run):
        import jax
        self.run, self.phase = run, "setup"
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in (COMPILE_EVENT, LOWER_EVENT):
            self.run.compiles.append((self.phase, event, float(duration)))

    def count(self, phase: str, event: str) -> tuple:
        hits = [d for p, e, d in self.run.compiles
                if p == phase and e == event]
        return len(hits), sum(hits)


# -- the run ------------------------------------------------------------------------

def devices_for(chips: int, allow_cpu: bool):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" and not allow_cpu:
        raise NoDevice(f"JAX finds no TPU (platform "
                       f"{devices[0].platform!r}); the benchmark runs on "
                       "the chip only")
    if len(devices) < chips:
        raise NoDevice(f"the cell asks for {chips} chips and JAX finds "
                       f"{len(devices)}")
    return devices[:chips]


def enable_cache() -> None:
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def draw_data(config: dict, seed: int):
    """(A, y) on the default device, uncommitted, so that a program over a
    mesh may place them. A configuration whose data block has a
    ``draw_seed`` is one fixed data set: the run's seed draws the order of
    its samples."""
    import jax
    import jax.numpy as jnp
    from bench import datagen

    data = config["data"]
    if "draw_seed" not in data:
        return datagen.draw(data, seed)
    a, y = datagen.draw(data, int(data["draw_seed"]))
    order = jax.random.permutation(datagen.key_from_seed(seed), a.shape[0])
    return jax.jit(lambda a, y, p: (jnp.take(a, p, axis=0), y[p]))(a, y,
                                                                    order)


def window(solve, seconds: float, run: Run):
    """Whole solves back to back until ``seconds`` have passed; the solve in
    flight at the deadline is finished and counted. Each solve's x stays
    on the host; returns the last solve's outcome (None if none came)."""
    from jax.profiler import TraceAnnotation
    last = None
    with TraceAnnotation("bench.window"):
        begin = time.perf_counter()
        while True:
            t0 = time.perf_counter()
            try:
                with TraceAnnotation("bench.solve"):
                    out = solve()
            except Exception as e:   # counted as failed, its time kept
                run.solves.append(Solve(time.perf_counter() - t0, 0, 0, None,
                                        False, f"{type(e).__name__}: {e}"))
                log(f"solve failed: {run.solves[-1].error}")
            else:
                run.solves.append(Solve(time.perf_counter() - t0, out.rounds,
                                        out.records, out.stop_round,
                                        out.certified, x=out.x))
                last = out
            if time.perf_counter() - begin >= seconds:
                break
        run.window_s = time.perf_counter() - begin
    return last


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             root: Path = ROOT, system: str = "program",
             allow_cpu: bool = False, start: float | None = None,
             resolved: dict | None = None) -> dict:
    """One run of ``workload``; returns the result object. ``resolved``
    replaces what ``BENCHMARK.json`` names (the tests' small copies)."""
    start = time.perf_counter() if start is None else start
    r = resolved or resolve(load_spec(root), workload, root)
    cell, config, traffic = r["cell"], r["config"], r["traffic"]
    import jax
    from bench import check, systems

    devices = devices_for(int(cell["chips"]), allow_cpu)
    run = Run(cell=cell, config=config, traffic=traffic,
              chips=len(devices), device_kind=devices[0].device_kind)
    compiles = CompileLog(run)

    a, y = draw_data(config, seed)
    jax.block_until_ready((a, y))
    solve, release = systems.SYSTEMS[system](config, traffic, a, y, devices)
    warm = solve()                       # every shape of the window
    log(f"warm-up solve: {warm.rounds} rounds, stop {warm.stop_round}")
    del warm
    run.setup_s = time.perf_counter() - start

    compiles.phase = "window"
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 1
        options.host_tracer_level = 2
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=options)
    try:
        last = window(solve, seconds, run)
    finally:
        if trace:
            jax.profiler.stop_trace()
    compiles.phase = "check"
    run.peak_bytes = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in devices]
    n_comp, s_comp = compiles.count("window", COMPILE_EVENT)
    n_low, s_low = compiles.count("window", LOWER_EVENT)
    log(f"inside the window: {n_comp} compilations ({s_comp:.4f} s), "
        f"{n_low} lowerings ({s_low:.4f} s), {len(run.solves)} solves")
    log("solve seconds: " + " ".join(f"{s.seconds:.4f}" for s in run.solves))

    release()
    del solve, release
    gc.collect()
    numbers = {}
    if last is not None:
        numbers = check.compare(config, traffic, a, y,
                                [s for s in run.solves if s.error is None],
                                last, devices[0])
    correct = (bool(numbers) and check.passes(numbers)
               and len(run.ok()) == len(run.solves))

    if trace:
        from bench import trace_reduce
        names = [f"/device:TPU:{d.id}" for d in devices]
        try:
            run.trace = trace_reduce.summarize(
                trace_reduce.load(str(TRACE_DIR)),
                names if devices[0].platform == "tpu" else None)
        except ValueError as e:   # no device op to read
            log(f"trace: {e}")
    wanted = r["per_layer"] if trace else r["end_to_end"]
    kind = "layer_metrics" if trace else "end_to_end"
    metrics = {}
    for m in wanted:
        value = reader(kind, m["name"], root)(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": int(max(run.peak_bytes))}
    result = {"correct": correct, "attempted": len(run.solves),
              "failed": len(run.solves) - len(run.ok()), "metrics": metrics,
              "device": device}
    if trace and run.trace is not None:
        devs = run.trace.devices
        # the driver reads idle = 1 - busy_s / window_s: both over the part
        # of the window each device's trace covers, averaged over devices
        device["busy_s"] = sum(d.busy_s for d in devs) / len(devs)
        device["window_s"] = sum(d.window_s for d in devs) / len(devs)
        for dev in devs:
            log(f"{dev.name}: busy {dev.busy_s:.6f} s of {dev.window_s:.6f} s "
                f"(its trace covers {run.trace.covered(dev):.6f} of the "
                f"{run.trace.window_s:.6f} s window), idle share "
                f"{run.trace.idle_share(dev):.6f}; {dev.events[0]} op and "
                f"{dev.events[1]} program events recorded")
        result["breakdown"] = {
            "device_ops": [list(kv) for kv in run.trace.top_ops(10)],
            "idle_gaps": [list(kv) for kv in run.trace.gap_labels(10)]}
    result["check"] = {name: {"value": v, "limit": lim}
                       for name, (v, lim) in numbers.items()}
    return result
