"""Each per-layer metric reader computes its number from a reading, and
reads nothing where it finds nothing to read."""
from __future__ import annotations

import importlib

import pytest

from bench import run, trace

KM = "kmeans-paper-100m.resident"
PEAKS = {"hbm_bytes_per_s": 819e9}


def _op(name, start, end, device="/device:TPU:0"):
    return trace.Op(device, name, float(start), float(end), name)


def _reading(cell_name, ops, *, jobs=2, steps=20, window_s=1e-6, chips=1,
             counters=None, peaks=PEAKS):
    cell = run.load_cell(cell_name)
    t = trace.Trace(trace._mark_parents(ops), [], (0.0, window_s * 1e9))
    c = {"compiles": 0, "program_compiles": 0, "jax_compile_events": 0,
         "host_syncs": 6}
    c.update(counters or {})
    return run.Reading(cell, chips, jobs, steps, 0, window_s, c, peaks, t)


def _read(metric, reading):
    return importlib.import_module(f"bench.metrics.{metric}").read(reading)


def test_idle_share_is_one_minus_busy_over_window():
    r = _reading(KM, [_op("fusion.1", 0, 250), _op("fusion.2", 500, 750)])
    assert _read("device_idle_pct", r) == pytest.approx(50.0)


def test_segment_kernel_roofline_uses_the_kernel_time_and_operand_bytes():
    cell = run.load_cell(KM)
    cfg = cell.config
    passes = 2 * (cfg["steps_per_job"] + 1)
    least_s = cell.job.segment_kernel_bytes(cfg) * passes / 819e9
    # the kernel took four times its least time
    ops = [_op("segment_reduce.7", 0, least_s * 4e9), _op("fusion.3", 0, 1)]
    r = _reading(KM, ops, jobs=2, window_s=least_s * 8)
    assert _read("segment_kernel_roofline_pct", r) == pytest.approx(25.0)
    assert _read("segment_kernel_roofline_pct",
                 _reading(KM, [_op("fusion.3", 0, 1)])) is None


def test_pass_roofline_is_least_step_time_over_measured_step_time():
    cell = run.load_cell(KM)
    least_s = cell.job.step_bytes(cell.config) / 819e9
    r = _reading(KM, [], steps=10, window_s=least_s * 10 * 50)
    assert _read("pass_hbm_roofline_pct", r) == pytest.approx(2.0)


def test_busy_shares_and_counters():
    ops = [_op("while.1", 0, 1000), _op("sort.2", 0, 400), _op("fusion.3", 400, 800)]
    r = _reading("wordcount-text-32k.stream", ops, steps=8)
    assert _read("sort_busy_pct", r) == pytest.approx(40.0)
    assert _read("host_syncs_per_step", r) == pytest.approx(0.75)
    assert _read("window_compiles", r) == 0
    r = _reading(KM, [], counters={"program_compiles": 1, "jax_compile_events": 2})
    assert _read("window_compiles", r) == 3


def test_collective_share_averages_over_chips():
    ops = [_op("all-to-all.1", 0, 100, "/device:TPU:0"),
           _op("fusion.1", 100, 400, "/device:TPU:0"),
           _op("fusion.1", 0, 400, "/device:TPU:1")]
    r = _reading("wordcount-text-32k.stream", ops, chips=2)
    assert _read("collective_busy_pct", r) == pytest.approx(12.5)


@pytest.mark.parametrize("metric", ["device_idle_pct", "sort_busy_pct",
                                    "segment_kernel_roofline_pct",
                                    "collective_busy_pct"])
def test_trace_metrics_read_nothing_without_a_trace(metric):
    r = _reading(KM, [])
    r.trace = None
    assert _read(metric, r) is None
