#!/usr/bin/env python3
"""Blaze's benchmark: one run of one cell on the chip.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration and a traffic mix.  Everything the run needs is found by name:

* ``bench/configs/<config>.json``: the deployment's sizes, its source, its
  cuts, the job kind (``kind``) and the limits of the comparison;
* ``bench/traffic/<config>.<traffic>.json``: how its jobs are driven;
* ``bench/jobs/<kind>.py``: the data generator, the code that builds the
  program the window drives, the plain reference and the comparison;
* ``bench/metrics/<metric>.py``: one reader per per-layer metric.

A run: set-up (device check, data made on the device from ``--seed``, the
program built, one whole job run to compile and warm every shape the window
uses), then the window: whole jobs back to back until ``--seconds`` have
passed, each ending with its result on the host.  Then the device's memory
peak is read, the program is freed, and every answer of the window is
compared with the plain reference.  The last line of standard output is the
result as one JSON object; the numbers compared, each beside its limit, are
the last lines of standard error and the last key of that object.

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics, read from a profiler trace of the window and from the
session's counters.  A run that finds no TPU, or fewer chips than the cell
asks for, exits 2 and prints no result.
"""
from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")

# JAX's compile-phase events: lowering to MLIR and the backend compile.
COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


class NoDevice(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


def span(name: str):
    """A host span of the benchmark, on the profiler's clock."""
    import jax

    return jax.profiler.TraceAnnotation("bench." + name)


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    """One cell and the files it names."""

    name: str
    chips: int
    config: dict
    traffic: dict
    job: object  # the bench.jobs.<kind> module
    end_to_end: list
    per_layer: list


def load_spec(bench_dir: str = BENCH) -> dict:
    return _read_json(os.path.join(os.path.dirname(bench_dir), "BENCHMARK.json"))


def load_cell(name: str, bench_dir: str = BENCH) -> Cell:
    """Find a cell of ``BENCHMARK.json`` and everything it names."""
    spec = load_spec(bench_dir)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    return cell_from(spec, cells[name], bench_dir)


def cell_from(spec: dict, w: dict, bench_dir: str = BENCH) -> Cell:
    """The cell of the ``workloads`` entry ``w``, its files read by name."""
    name = w["name"]
    config = _read_json(os.path.join(bench_dir, "configs", w["config"] + ".json"))
    traffic = _read_json(os.path.join(
        bench_dir, "traffic", f"{w['config']}.{w['traffic']}.json"
    ))
    job = importlib.import_module(f"bench.jobs.{config['kind']}")

    def mine(m):
        # A metric without ``workloads`` is reported in every cell that
        # reports the end-to-end metric it moves.
        if "workloads" in m:
            return name in m["workloads"]
        moved = {e["name"]: e for e in spec["end_to_end"]}[m["moves"]]
        return "workloads" not in moved or name in moved["workloads"]

    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    per_layer = [m for m in spec["per_layer"] if mine(m)]
    return Cell(name, int(w["chips"]), config, traffic, job, e2e, per_layer)


def load_peaks(kind: str, bench_dir: str = BENCH) -> dict:
    """The peaks of ``device_kind``; a device not in the table is an error."""
    table = _read_json(os.path.join(bench_dir, "peaks.json"))["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r}; known: {sorted(table)}")
    return table[kind]


@dataclasses.dataclass
class Reading:
    """What a per-layer metric reader gets: the cell, the window's counts
    and host time, the session's counters over the window, the peaks of the
    device, and the reduced trace (``None`` without ``--trace 1``)."""

    cell: Cell
    chips: int
    jobs: int
    steps: int
    records: int
    window_s: float
    counters: dict
    peaks: dict
    trace: object = None


class CompileEvents:
    """Counts JAX's compile events while ``on``."""

    def __init__(self):
        import jax

        self.on = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, duration, **_kw):
        if self.on and event in COMPILE_EVENTS:
            self.count += 1


SESSION_COUNTERS = ("compiles", "program_compiles", "host_syncs", "retries",
                    "degraded_nodes")


def _counters(sess) -> dict:
    return {k: getattr(sess.stats, k) for k in SESSION_COUNTERS}


def check_devices(chips: int, allow_cpu: bool = False) -> list:
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" and not allow_cpu:
        raise NoDevice(f"no TPU: JAX found {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX found {len(devices)}")
    return devices


def enable_cache() -> str:
    """JAX's persistent compile cache at the checkout's fixed ``.jax_cache``
    (given to the program, which takes ``JAX_COMPILATION_CACHE_DIR``), with
    every program kept, however short its compile."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)  # never evict
    return path


def memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             allow_cpu: bool = False, cache: bool = True,
             t_start: float | None = None, keep_trace: str | None = None) -> dict:
    """One run of ``cell``; returns the result object (see the module doc).
    ``allow_cpu`` and ``cache=False`` are for the tests, ``keep_trace`` (a
    directory) keeps the raw trace for a look by hand."""
    t_start = _T_START if t_start is None else t_start
    devices = check_devices(cell.chips, allow_cpu)
    import jax
    from repro.core import BlazeSession, data_mesh

    if cache:
        enable_cache()
    compile_events = CompileEvents()
    mesh = data_mesh(cell.chips)
    used = list(mesh.devices.flat)
    mod = cell.job
    marks = [("start", t_start), ("devices", time.perf_counter())]
    data = mod.generate(cell.config, cell.traffic, seed, mesh)
    jax.block_until_ready(list(vars(data).values()))
    marks.append(("data", time.perf_counter()))
    sess = BlazeSession(mesh)
    job = mod.build(sess, data, cell.config, cell.traffic, mesh, span)
    with span("warmup"):
        job.run(0)  # compiles, and warms every shape the window uses
    marks.append(("warm job", time.perf_counter()))
    print("setup: " + ", ".join(
        f"{b[0]} {b[1] - a[1]:.2f} s" for a, b in zip(marks, marks[1:])
    ), file=sys.stderr)
    tracedir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        jax.profiler.start_trace(tracedir)
    c0 = _counters(sess)
    compile_events.on = True
    answers, failed, records, steps = {}, 0, 0, 0
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    with span("window"):
        j = 1
        while True:
            try:
                answers[j] = job.run(j)
                records += job.records(j)
                steps += job.steps_per_job
            except Exception:  # noqa: BLE001 — counted, reported, and fails the run
                traceback.print_exc()
                failed += 1
                break
            j += 1
            if time.perf_counter() - t0 >= seconds:
                break
    window_s = time.perf_counter() - t0
    compile_events.on = False
    attempted = j if failed else j - 1
    c1 = _counters(sess)
    counters = {k: c1[k] - c0[k] for k in SESSION_COUNTERS}
    counters["jax_compile_events"] = compile_events.count
    reduced = None
    if trace:
        jax.profiler.stop_trace()
        from bench import trace as trace_mod

        try:
            reduced = trace_mod.load(tracedir)
            if keep_trace:
                shutil.copytree(tracedir, keep_trace, dirs_exist_ok=True)
        finally:
            shutil.rmtree(tracedir, ignore_errors=True)
    peak = memory_peak(used)
    # Free the program and its state before the reference runs.
    del job, sess
    gc.collect()

    checks = {"raised_jobs": (failed, 0),
              "degraded_nodes": (counters["degraded_nodes"], 0),
              "retries": (counters["retries"], 0)}
    limits = cell.config["limits"]
    worst, wrong = mod.check(data, cell.config, cell.traffic, answers, limits)
    for name, value in worst.items():
        checks[name] = (value, limits[name])
    failed += wrong
    correct = bool(answers) and all(v <= lim for v, lim in checks.values())

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    if not trace:
        metrics = {}
        for m in cell.end_to_end:
            if m["name"] == "setup_s":
                value = setup_s
            elif m["name"] == "records_per_s":
                value = records / window_s
            else:
                raise KeyError(f"no end-to-end metric {m['name']!r}")
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        reading = Reading(cell, cell.chips, len(answers), steps, records,
                          window_s, counters,
                          load_peaks(dev.device_kind) if not allow_cpu else {},
                          reduced)
        metrics = {}
        for m in cell.per_layer:
            reader = importlib.import_module(f"bench.metrics.{m['name']}")
            value = reader.read(reading)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = reduced.busy_s()
        device["window_s"] = reduced.window_s
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if trace:
        out["breakdown"] = {"device_ops": reduced.top_ops(10),
                            "idle_gaps": reduced.idle_by_span(10)}
    out["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", metavar="DIR",
                    help="also copy the raw trace of --trace 1 to DIR")
    args = ap.parse_args(argv)
    for p in (ROOT, os.path.join(ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    cell = load_cell(args.workload)
    try:
        out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                       keep_trace=args.keep_trace)
    except NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
