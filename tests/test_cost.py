"""Cost-model unit tests: the shared candidate grids are bit-compatible with
the kernels' greedy sizing picks, and the calibrated fallback model
reproduces the PR 2 static engine rule exactly.
"""
import jax.numpy as jnp
import pytest

from repro.core import cost
from repro.core.plan import (
    PALLAS_AUTO_MAX_KEYS,
    node_key_count,
    resolve_engine,
)
from repro.core.reducers import get_reducer
from repro.kernels import hash_combine as HK
from repro.kernels import segment_reduce as SK


# -- candidate grids == the kernels' greedy sizing picks ---------------------


@pytest.mark.parametrize("reducer", ["sum", "min", "max", "prod"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32, jnp.bfloat16])
@pytest.mark.parametrize("k,v", [(4, 1), (64, 8), (512, 4), (4096, 128)])
def test_choose_block_n_is_grid_pick(reducer, dtype, k, v):
    for n in (1, 7, 100, 5000):
        grid = cost.segment_block_candidates(n, k, v, reducer, dtype)
        # ascending powers of two from one 128-lane tile, within budget
        assert [bn for bn, _ in grid] == sorted({bn for bn, _ in grid})
        assert grid[0][0] == cost.LANES
        for bn, ws in grid[1:]:
            assert bn & (bn - 1) == 0 and ws <= cost.VMEM_BUDGET
        # the kernel delegate picks the largest candidate, clamped to the
        # stream's lane-padded length
        lanes_n = -(-n // cost.LANES) * cost.LANES
        assert SK.choose_block_n(n, k, v, reducer, dtype) == min(
            grid[-1][0], lanes_n
        )


@pytest.mark.parametrize("reducer", ["sum", "min"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32])
@pytest.mark.parametrize("v", [1, 4, 64])
def test_choose_table_cap_is_grid_pick(reducer, dtype, v):
    for n in (1, 100, 4096):
        for hint in (None, 50, 1000):
            grid = cost.hash_table_candidates(
                n, v, reducer, dtype, distinct_hint=hint
            )
            cap0 = grid[0][0]
            assert all(c == cap0 for c, _, _, _ in grid)  # cap fixed first
            assert all(
                p == cost.choose_probe_depth(n, cap0) for _, _, p, _ in grid
            )
            got = HK.choose_table_cap(
                n, v, reducer, dtype, distinct_hint=hint
            )
            cap, bn, probes, _ = grid[-1]
            lanes_n = -(-n // cost.LANES) * cost.LANES
            assert got == (cap, min(bn, lanes_n), probes)


def test_kernel_delegates_share_one_implementation():
    assert SK.choose_block_n(10_000, 128, 8) == cost.choose_block_n(
        10_000, 128, 8
    )
    assert HK.choose_probe_depth(100, 256) == cost.choose_probe_depth(100, 256)
    assert HK.choose_table_cap(100, 4) == cost.choose_table_cap(100, 4)


def test_hash_working_set_monotone_in_block():
    ws = [
        cost.hash_working_set(512, bn, 4) for bn in (128, 256, 512, 1024)
    ]
    assert ws == sorted(ws)
    # a narrow trailing dim still occupies a whole 128-lane tile
    assert cost.hash_working_set(512, 128, 1) == cost.hash_working_set(
        512, 128, 8
    )


# -- calibrated fallback model == the PR 2 static rule -----------------------


def test_pick_engine_crossover_is_the_pr2_threshold():
    # the PR 2 matrix: the static rule was ``pallas iff 0 < K <= 4096``
    for k in (1, 2, 100, 4095, 4096, 4097, 8192, 1 << 20):
        want = "pallas" if k <= PALLAS_AUTO_MAX_KEYS else "eager"
        assert cost.pick_engine(k) == want, k
    assert cost.pick_engine(0) == "eager"
    assert cost.pick_engine(-1) == "eager"


@pytest.mark.parametrize("k", [16, 4096, 4097, 100_000])
def test_resolve_engine_auto_matches_model(k):
    red = get_reducer("sum")
    target = jnp.zeros((k, 2), jnp.float32)
    assert node_key_count(target) == k
    assert resolve_engine("auto", target, red) == cost.pick_engine(k)


def test_node_cost_orders_engines():
    # naive is always modelled worst; crossover ordering flips at 4096
    for k in (10, 4096, 5000):
        assert cost.node_cost("naive", k) > cost.node_cost("eager", k)
        assert cost.node_cost("naive", k) > cost.node_cost("pallas", k)
    assert cost.node_cost("pallas", 100) < cost.node_cost("eager", 100)
    assert cost.node_cost("pallas", 10_000) > cost.node_cost("eager", 10_000)
