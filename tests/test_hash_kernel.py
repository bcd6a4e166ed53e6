"""Unit tests for the Pallas hash-aggregation kernel (interpret mode on CPU):
dict-oracle differentials over the full reducer monoid, init-table merges,
probe/overflow semantics, the capacity autotuner, and parity of the kernel's
hash/sentinel with the containers they must agree with."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import containers as C
from repro.kernels import hash_combine as HK

rng = np.random.RandomState(0)

REDUCERS = ("sum", "prod", "min", "max")

_NP_FN = {
    "sum": np.add,
    "prod": np.multiply,
    "min": np.minimum,
    "max": np.maximum,
}


def _dict_oracle(keys, vals, reducer, dead=None):
    out: dict = {}
    fn = _NP_FN[reducer]
    for i, (k, v) in enumerate(zip(keys.tolist(), vals.tolist())):
        if dead is not None and dead[i]:
            continue
        out[k] = fn(out[k], v) if k in out else v
    return out


def _table_dict(tkeys, tvals):
    tkeys, tvals = np.asarray(tkeys), np.asarray(tvals)
    return {
        int(k): tvals[i, 0]
        for i, k in enumerate(tkeys)
        if k != HK.EMPTY_KEY
    }


def test_kernel_hash_and_sentinel_match_containers():
    """The kernel-side splitmix32 copy and EMPTY_KEY must agree with
    repro.core.containers — slot placement must be bit-identical."""
    assert HK.EMPTY_KEY == C.EMPTY_KEY
    xs = jnp.asarray(
        np.concatenate([rng.randint(-(2**31), 2**31 - 1, 4096),
                        np.arange(-64, 64)]).astype(np.int32)
    )
    np.testing.assert_array_equal(
        np.asarray(HK.hash32(xs)), np.asarray(C.hash32(xs))
    )


@pytest.mark.parametrize("dtype_name", ("f32", "i32", "bf16"))
@pytest.mark.parametrize("reducer", REDUCERS)
def test_kernel_matches_dict_oracle(reducer, dtype_name):
    dtype = {"f32": jnp.float32, "i32": jnp.int32, "bf16": jnp.bfloat16}[
        dtype_name
    ]
    n = 257  # not a block multiple: exercises the padded tail
    keys = rng.randint(0, 60, n).astype(np.int32)
    if reducer == "prod":
        vals = rng.choice([1.0, -1.0], n)
        vals[rng.rand(n) < 0.1] = 2.0
    else:
        vals = rng.randint(-8, 9, n).astype(np.float64)
    dead = rng.rand(n) < 0.25
    mkeys = np.where(dead, HK.EMPTY_KEY, keys).astype(np.int32)
    jvals = jnp.asarray(vals[:, None]).astype(dtype)

    tk, tv, ovf = HK.hash_aggregate(
        jnp.asarray(mkeys), jvals, 256, reducer=reducer, block_n=64
    )
    assert int(ovf) == 0
    got = _table_dict(tk, tv)
    want = _dict_oracle(
        keys, np.asarray(jnp.asarray(vals).astype(dtype), np.float64),
        reducer, dead,
    )
    assert set(got) == set(want)
    tol = 0.25 if dtype_name == "bf16" else 1e-5
    for k in want:
        assert abs(float(got[k]) - want[k]) <= tol, (reducer, dtype_name, k)


@pytest.mark.parametrize("reducer", ("sum", "min"))
def test_kernel_init_merge_equals_two_pass(reducer):
    """Merging stream B into the table built from stream A == aggregating
    A ++ B in one pass (the post-shuffle merge contract)."""
    ka = rng.randint(0, 40, 100).astype(np.int32)
    kb = rng.randint(0, 40, 80).astype(np.int32)
    va = rng.randint(-9, 10, (100, 1)).astype(np.float32)
    vb = rng.randint(-9, 10, (80, 1)).astype(np.float32)
    cap = 128
    tk_a, tv_a, ovf_a = HK.hash_aggregate(
        jnp.asarray(ka), jnp.asarray(va), cap, reducer=reducer
    )
    tk_m, tv_m, ovf_m = HK.hash_aggregate(
        jnp.asarray(kb), jnp.asarray(vb), cap, reducer=reducer,
        init=(tk_a, tv_a, ovf_a),
    )
    tk_1, tv_1, _ = HK.hash_aggregate(
        jnp.asarray(np.concatenate([ka, kb])),
        jnp.asarray(np.concatenate([va, vb])), cap, reducer=reducer,
    )
    assert int(ovf_m) == 0
    assert _table_dict(tk_m, tv_m) == _table_dict(tk_1, tv_1)


def test_kernel_matches_hashmap_insert_layout():
    """Same probe sequence as containers.hashmap_insert: inserting a unique
    batch lands every key in the same slot either way."""
    cap = 64
    keys = np.unique(rng.randint(0, 10_000, 80).astype(np.int32))[:40]
    vals = np.arange(len(keys), dtype=np.float32) + 1.0
    red = __import__(
        "repro.core.reducers", fromlist=["get_reducer"]
    ).get_reducer("sum")
    ref = C.make_table(cap, (), jnp.float32, red)
    ref = C.hashmap_insert(
        ref, jnp.asarray(keys), jnp.asarray(vals),
        jnp.ones(len(keys), bool), red,
    )
    tk, tv, ovf = HK.hash_aggregate(
        jnp.asarray(keys), jnp.asarray(vals[:, None]), cap, reducer="sum",
        max_probes=16,
    )
    np.testing.assert_array_equal(np.asarray(tk), np.asarray(ref.keys))
    np.testing.assert_allclose(
        np.asarray(tv[:, 0]), np.asarray(ref.vals), rtol=1e-6
    )
    assert int(ovf) == int(ref.overflow)


def test_kernel_duplicates_within_one_block_fold():
    """Every lane the same key — the whole block must fold into one row in
    a single probe round (the unique_combine-free claim)."""
    n = 64
    keys = np.full(n, 7, np.int32)
    vals = np.ones((n, 1), np.float32)
    tk, tv, ovf = HK.hash_aggregate(
        jnp.asarray(keys), jnp.asarray(vals), 32, reducer="sum", block_n=64
    )
    got = _table_dict(tk, tv)
    assert got == {7: pytest.approx(64.0)} and int(ovf) == 0


def test_kernel_overflow_counted_never_silent():
    """More distinct keys than table slots: drops are counted exactly and
    surviving rows still hold their exact totals."""
    keys = np.arange(64, dtype=np.int32)
    vals = np.full((64, 1), 3.0, np.float32)
    tk, tv, ovf = HK.hash_aggregate(
        jnp.asarray(keys), jnp.asarray(vals), 16, reducer="sum", max_probes=16
    )
    live = int((np.asarray(tk) != HK.EMPTY_KEY).sum())
    assert live <= 16
    assert live + int(ovf) == 64  # conservation, nothing silent
    for k, v in _table_dict(tk, tv).items():
        assert v == pytest.approx(3.0)


def test_kernel_empty_and_all_dead_streams():
    cap = 64
    tk, tv, ovf = HK.hash_aggregate(
        jnp.zeros((0,), jnp.int32), jnp.zeros((0, 1), jnp.float32), cap
    )
    assert int((np.asarray(tk) != HK.EMPTY_KEY).sum()) == 0 and int(ovf) == 0
    dead = jnp.full((32,), HK.EMPTY_KEY, jnp.int32)
    tk, tv, ovf = HK.hash_aggregate(dead, jnp.ones((32, 1), jnp.float32), cap)
    assert int((np.asarray(tk) != HK.EMPTY_KEY).sum()) == 0 and int(ovf) == 0


def test_kernel_multiblock_stream_equals_single_block():
    """Block size changes insertion order (and therefore may permute which
    slot a colliding key lands in) but never the aggregated *content*."""
    keys = rng.randint(0, 100, 512).astype(np.int32)
    vals = rng.randn(512, 2).astype(np.float32)
    small = HK.hash_aggregate(
        jnp.asarray(keys), jnp.asarray(vals), 256, reducer="sum", block_n=32
    )
    big = HK.hash_aggregate(
        jnp.asarray(keys), jnp.asarray(vals), 256, reducer="sum", block_n=512
    )

    def as_dict(tk, tv):
        tk, tv = np.asarray(tk), np.asarray(tv)
        return {
            int(k): tuple(np.round(tv[i], 4))
            for i, k in enumerate(tk) if k != HK.EMPTY_KEY
        }

    assert int(small[2]) == int(big[2]) == 0
    a, b = as_dict(*small[:2]), as_dict(*big[:2])
    assert set(a) == set(b)
    for k in a:
        np.testing.assert_allclose(a[k], b[k], rtol=1e-4, atol=1e-4)


def test_kernel_interpret_flag_equivalence():
    """interpret=True (forced) and the default resolution produce identical
    tables — off the chip the default resolves to interpret mode."""
    keys = rng.randint(0, 30, 128).astype(np.int32)
    vals = rng.randn(128, 1).astype(np.float32)
    a = HK.hash_aggregate(
        jnp.asarray(keys), jnp.asarray(vals), 128, reducer="sum",
        interpret=True,
    )
    b = HK.hash_aggregate(
        jnp.asarray(keys), jnp.asarray(vals), 128, reducer="sum"
    )
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
    np.testing.assert_allclose(np.asarray(a[1]), np.asarray(b[1]), rtol=1e-6)


def test_choose_table_cap_autotuner():
    # power-of-two capacity targeting load factor <= 0.5
    cap, bn, probes = HK.choose_table_cap(100, 1)
    assert cap >= 200 and (cap & (cap - 1)) == 0
    assert bn == 128 and probes == 16
    # a distinct-key hint shrinks the table below the stream length
    cap_h, _, _ = HK.choose_table_cap(100_000, 1, distinct_hint=500)
    assert cap_h == 1024
    # VMEM budget caps capacity; load factor rises, probe depth follows
    cap_b, bn_b, probes_b = HK.choose_table_cap(
        1_000_000, 8, vmem_budget=1 << 20
    )
    assert cap_b * 128 * 2 * 4 <= (1 << 20)  # keys + values, lane-padded
    assert probes_b > 16
    # probe depth never exceeds the table
    assert HK.choose_probe_depth(10, 4) <= 4


# (reducer, value dtype): integer counts, integer-valued f32 sums (exact in
# any fold order) and f32 maxima.  Keys span _HGRID_DISTINCT values, the
# ``distinct_hint`` that fixes the grid's capacity.
_HGRID_SETTINGS = {
    "i32-sum": ("sum", np.int32),
    "f32-sum": ("sum", np.float32),
    "f32-max": ("max", np.float32),
}
_HGRID_N, _HGRID_DISTINCT = 2048, 50


def _hash_grid_cases():
    from repro.core import cost

    return [
        pytest.param(name, cap, bn, probes, id=f"{name}-cap{cap}-bn{bn}")
        for name, (red, dt) in _HGRID_SETTINGS.items()
        for cap, bn, probes, _ws in cost.hash_table_candidates(
            _HGRID_N, 1, red, dt, distinct_hint=_HGRID_DISTINCT
        )
    ]


@pytest.mark.parametrize("setting,cap,block_n,probes", _hash_grid_cases())
def test_kernel_same_contents_at_every_grid_point(setting, cap, block_n, probes):
    """The kernel's table does not depend on its launch geometry: every
    ``(cap, block_n, probes)`` of ``cost.hash_table_candidates`` holds the
    dict oracle's contents, with nothing overflowed."""
    reducer, dtype = _HGRID_SETTINGS[setting]
    r = np.random.RandomState(11)
    keys = r.randint(0, _HGRID_DISTINCT, _HGRID_N).astype(np.int32)
    if reducer == "sum":
        vals = r.randint(-20, 21, _HGRID_N).astype(dtype)
    else:
        vals = r.randn(_HGRID_N).astype(dtype)
    tk, tv, ovf = HK.hash_aggregate(
        jnp.asarray(keys), jnp.asarray(vals[:, None]), cap, reducer=reducer,
        max_probes=probes, block_n=block_n,
    )
    assert int(ovf) == 0
    want = _dict_oracle(keys, vals, reducer)
    got = _table_dict(tk, tv)
    assert got.keys() == want.keys()
    for k, v in want.items():
        assert got[k] == v, (k, got[k], v)


def test_kernel_lanes_accounting():
    # blocks are whole 128-lane tiles: a request below one tile rounds up
    bn, lanes = HK.hash_aggregate_lanes(100, 256, 1, block_n=64)
    assert bn == 128 and lanes == 128
    bn2, lanes2 = HK.hash_aggregate_lanes(300, 256, 1, block_n=128)
    assert bn2 == 128 and lanes2 == 384
