"""The harness's comparison catches each fault a cell can have: a run at a
tiny size on the CPU, driven whole by the harness with the timed path
broken underneath, reads ``correct`` false; the sound run reads true."""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import pytest

from bench.tests import faults

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
KINDS = ["wordcount", "kmeans", "pagerank"]


@pytest.mark.parametrize("kind", KINDS)
def test_sound_run_is_correct(kind):
    out = faults.run_tiny(kind)
    assert out["correct"] is True and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert all(c["value"] <= c["limit"] for c in out["checks"].values())


@pytest.mark.parametrize("kind", KINDS)
def test_state_left_unchanged_is_caught(kind, monkeypatch):
    faults.freeze_state(monkeypatch)
    out = faults.run_tiny(kind)
    assert out["correct"] is False and out["failed"] >= 1


@pytest.mark.parametrize("kind", KINDS)
def test_half_the_batch_left_out_is_caught(kind, monkeypatch):
    faults.half_batch(monkeypatch, kind)
    out = faults.run_tiny(kind)
    assert out["correct"] is False and out["failed"] >= 1


@pytest.mark.parametrize("kind", KINDS)
def test_an_altered_answer_is_caught(kind, monkeypatch):
    faults.alter_answer(monkeypatch, kind)
    out = faults.run_tiny(kind)
    assert out["correct"] is False and out["failed"] >= 1


SHUFFLE = textwrap.dedent("""
    import json, os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path[:0] = [{root!r}, os.path.join({root!r}, "src")]
    import pytest
    from bench.tests import faults
    out = {{}}
    out["sound"] = faults.run_tiny("wordcount4")["correct"]
    with pytest.MonkeyPatch.context() as mp:
        faults.drop_exchange(mp)
        out["no_exchange"] = faults.run_tiny("wordcount4")["correct"]
    print(json.dumps(out))
""")


def test_the_exchange_between_chips_left_out_is_caught():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run([sys.executable, "-c", SHUFFLE.format(root=ROOT)],
                       capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    got = __import__("json").loads(p.stdout.strip().splitlines()[-1])
    assert got == {"sound": True, "no_exchange": False}
