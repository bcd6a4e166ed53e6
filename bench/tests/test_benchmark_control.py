"""The control of each cell's comparison fails it: the plain reference one
precision below the configuration's (bfloat16 for float32, int16 for int32
counts), put in the program's place, breaks the limits the configurations
hold.  At a test size on the CPU; the readings at the cells' own sizes, on
the chip, are in PERF.md."""
from __future__ import annotations

import pytest

from bench import control
from bench.tests import faults
from repro.core import data_mesh

SIZES = {
    # a job's top word must pass 32,767 counts for int16 to wrap
    "wordcount": {"corpus_lines": 4096, "block_lines": 1024, "vocab": 512},
    "kmeans": {"n_points": 1 << 15, "init_pool": 256},
    "pagerank": {"scale": 10},
}


@pytest.mark.parametrize("kind", sorted(SIZES))
def test_control_breaks_a_limit(kind):
    cell = faults.cell(kind)
    cell.config.update(SIZES[kind])
    data = cell.job.generate(cell.config, cell.traffic, 2**31 + 1, data_mesh(1))
    nums = control.control_numbers(cell, data, jobs=1)
    limits = cell.config["limits"]
    assert set(nums) == set(limits)
    assert any(v > limits[k] for k, v in nums.items()), nums


def test_every_configuration_has_a_lower_precision():
    for kind in faults.CELLS:
        assert faults.cell(kind).config["dtype"] in control.LOWER
