"""The harness finds a cell's files by name: a configuration, a traffic mix
and a per-layer metric added as new files, plus new entries in
``BENCHMARK.json``, run without an edit to any file already there.  And a
device that is not in the table of peaks is an error."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

from bench import run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

NEW_METRIC = textwrap.dedent('''
    """Jobs the window completed (a metric a later change adds)."""


    def read(r):
        return float(r.jobs)
''')

DRIVE = textwrap.dedent("""
    import json, sys, time
    sys.path[:0] = [{root!r}, {src!r}]
    from bench import run
    cell = run.load_cell("pagerank-tiny.resident")
    out = run.run_cell(cell, 2**31 + 9, 0.0, True, allow_cpu=True, cache=False,
                       t_start=time.perf_counter())
    print(json.dumps({{"file": run.__file__, "out": out}}))
""")


def _snapshot(root: str) -> dict:
    snap = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                snap[os.path.relpath(p, root)] = fh.read()
    return snap


def test_a_new_cell_runs_from_new_files_alone(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = _snapshot(str(tmp_path / "bench"))

    bench = tmp_path / "bench"
    cfg = json.loads((bench / "configs" / "pagerank-graph500-s20.json").read_text())
    cfg.update(name="pagerank-tiny", scale=8, steps_per_job=4)
    (bench / "configs" / "pagerank-tiny.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "pagerank-tiny.resident.json").write_text(
        json.dumps({"why": "tiny", "unroll": 2, "engine": "eager"}))
    (bench / "metrics" / "jobs_done.py").write_text(NEW_METRIC)
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "pagerank-tiny", "source": "test",
                            "file": "bench/configs/pagerank-tiny.json",
                            "reduced": ["scale"], "why": "test"})
    spec["workloads"].append({"name": "pagerank-tiny.resident",
                              "config": "pagerank-tiny", "traffic": "resident",
                              "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "jobs_done", "unit": "jobs",
                              "better": "higher", "source": "program_counter",
                              "layer": "session and program",
                              "moves": "records_per_s",
                              "workloads": ["pagerank-tiny.resident"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    code = DRIVE.format(root=str(tmp_path), src=os.path.join(ROOT, "src"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, cwd=str(tmp_path), timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got["file"].startswith(str(tmp_path))
    out = got["out"]
    assert out["correct"] is True
    # the metrics whose workloads list the new cell, and no other
    assert set(out["metrics"]) == {"jobs_done"}
    assert out["metrics"]["jobs_done"]["value"] >= 1
    # no file that was there changed
    after = _snapshot(str(bench))
    assert {k: after[k] for k in before} == before


def test_cell_finds_its_own_metrics():
    cell = run.load_cell("kmeans-paper-100m.resident")
    names = {m["name"] for m in cell.per_layer}
    assert {"segment_kernel_roofline_pct", "pass_hbm_roofline_pct"} <= names
    assert "sort_busy_pct" not in names
    assert [m["name"] for m in cell.end_to_end] == ["records_per_s", "setup_s"]
    assert cell.job.__name__ == "bench.jobs.kmeans"
    spec = run.load_spec()
    for w in spec["workloads"]:
        c = run.load_cell(w["name"])
        assert c.chips == w["chips"] and c.per_layer
        assert all(w["name"] in m["workloads"] for m in c.per_layer)
    with pytest.raises(SystemExit):
        run.load_cell("no-such.cell")


def test_every_metric_has_its_reader():
    import importlib

    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for m in spec["per_layer"]:
        assert callable(importlib.import_module(f"bench.metrics.{m['name']}").read)


def test_peaks_are_known_for_v5e_only():
    peaks = run.load_peaks("TPU v5 lite")
    assert peaks["hbm_bytes_per_s"] == 819e9
    assert peaks["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        run.load_peaks("TPU v9 imaginary")


def test_no_tpu_means_no_run():
    with pytest.raises(run.NoDevice):
        run.check_devices(1)
