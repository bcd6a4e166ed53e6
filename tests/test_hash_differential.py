"""Adversarial hash-path differentials: every engine (eager / pallas / naive)
against a NumPy dict oracle on the streams that stress open addressing —
Zipfian skew, all-pairs-collide-to-one-slot, duplicate-heavy batches, and
table-near-capacity overflow — plus the wire-narrowing and stable-bucketing
satellites and the fused program-mode wordcount acceptance counters."""
import collections
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import BlazeSession, distribute, make_dist_hashmap
from repro.core import containers as C
from repro.core.mapreduce import bucket_by_dest
from repro.launch.simulate import simulated_env

ENGINES = ("eager", "pallas", "naive")

SESS = BlazeSession()


def _mapper(i, row, emit):
    emit(row[0].astype(jnp.int32), row[1], mask=row[2] > 0)


def _dict_oracle(keys, vals, mask, reducer="sum"):
    fn = {
        "sum": np.add, "prod": np.multiply,
        "min": np.minimum, "max": np.maximum,
    }[reducer]
    want: dict = {}
    for k, v, m in zip(keys.astype(np.int64), vals.astype(np.float64), mask):
        if m > 0:
            want[int(k)] = fn(want[int(k)], v) if int(k) in want else v
    return want


def _run(engine, keys, vals, mask, capacity, reducer="sum", **kw):
    rows = distribute(
        np.stack([keys, vals, mask], axis=1).astype(np.float32)
    )
    hm = make_dist_hashmap(SESS.mesh, capacity, (), jnp.float32, reducer)
    return SESS.map_reduce(
        rows, _mapper, reducer, hm, engine=engine, return_stats=True, **kw
    )


def _hash32_np(x: np.ndarray) -> np.ndarray:
    """Host-side splitmix32 (mirrors containers.hash32) for crafting
    collision sets."""
    with np.errstate(over="ignore"):
        x = x.astype(np.uint32)
        x = (x ^ (x >> np.uint32(16))) * np.uint32(0x7FEB352D)
        x = (x ^ (x >> np.uint32(15))) * np.uint32(0x846CA68B)
        return x ^ (x >> np.uint32(16))


def test_host_hash_mirror_is_faithful():
    xs = np.arange(-512, 512, dtype=np.int32)
    np.testing.assert_array_equal(
        _hash32_np(xs), np.asarray(C.hash32(jnp.asarray(xs)))
    )


@pytest.mark.parametrize("reducer", ("sum", "min", "prod"))
@pytest.mark.parametrize("engine", ENGINES)
def test_zipfian_keys_match_oracle(engine, reducer):
    """Heavy skew: a handful of keys hold most of the mass — the regime the
    eager/kernel local combine exists for."""
    rng = np.random.RandomState(5)
    n = 256
    keys = rng.zipf(1.3, n).clip(max=997).astype(np.float32)
    if reducer == "prod":
        vals = rng.choice([1.0, -1.0], n).astype(np.float32)
    else:
        vals = rng.randint(-8, 9, n).astype(np.float32)
    mask = (rng.rand(n) > 0.15).astype(np.float32)
    hm, st = _run(engine, keys, vals, mask, 4096, reducer)
    st = st.finalize()
    assert st.engine == engine and hm.total_overflow() == 0
    got = {int(k): float(v) for k, v in hm.to_dict().items()}
    want = _dict_oracle(keys, vals, mask, reducer)
    assert set(got) == set(want)
    for k in want:
        assert abs(got[k] - want[k]) < 1e-4, (engine, reducer, k)
    if engine == "pallas":
        assert st.kernel_table_cap is not None
        assert st.kernel_probe_depth >= 16
        assert 0.0 < st.kernel_occupancy <= 1.0


@pytest.mark.parametrize("engine", ENGINES)
def test_all_pairs_collide_to_one_slot(engine):
    """Keys crafted so EVERY pair hashes to the same home slot of the target
    table — worst-case linear-probe clustering.  With probe room available,
    every key must still land, exactly once, with exact sums."""
    cap = 64
    pool = np.arange(1, 200_000, dtype=np.int32)
    same_slot = pool[(_hash32_np(pool) % np.uint32(cap)) == 7][:20]
    assert len(same_slot) == 20
    keys = np.repeat(same_slot, 3).astype(np.float32)  # duplicates too
    vals = np.ones(len(keys), np.float32)
    mask = np.ones(len(keys), np.float32)
    hm, st = _run(engine, keys, vals, mask, cap)
    assert hm.total_overflow() == 0
    got = {int(k): float(v) for k, v in hm.to_dict().items()}
    assert got == {int(k): 3.0 for k in same_slot}


@pytest.mark.parametrize("engine", ENGINES)
def test_duplicate_heavy_batch_matches_oracle(engine):
    """64x duplication per key: the local combine must collapse the stream
    (eager/pallas ship <= distinct * shards pairs; naive ships all)."""
    rng = np.random.RandomState(9)
    n, n_keys = 512, 8
    keys = rng.randint(0, n_keys, n).astype(np.float32)
    vals = rng.randint(1, 5, n).astype(np.float32)
    mask = np.ones(n, np.float32)
    hm, st = _run(engine, keys, vals, mask, 128)
    st = st.finalize()
    got = {int(k): float(v) for k, v in hm.to_dict().items()}
    assert got == pytest.approx(_dict_oracle(keys, vals, mask))
    n_shards = SESS.mesh.shape["data"]
    if engine == "naive":
        assert st.pairs_shipped == n
    else:
        assert st.pairs_shipped <= n_keys * n_shards


@pytest.mark.parametrize("engine", ENGINES)
def test_near_capacity_overflow_invariants(engine):
    """More distinct keys than the table can hold: drops must be *counted*
    (live + overflow covers every distinct key), survivors must hold their
    exact oracle totals, and the table never exceeds capacity."""
    n = 96
    keys = np.arange(n, dtype=np.float32)
    vals = np.full(n, 2.0, np.float32)
    mask = np.ones(n, np.float32)
    cap = 16
    hm, st = _run(engine, keys, vals, mask, cap)
    st = st.finalize()
    n_shards = hm.n_shards
    assert hm.size() <= cap * n_shards
    assert hm.total_overflow() > 0
    assert hm.size() + hm.total_overflow() == n  # conservation, exact
    for k, v in hm.to_dict().items():
        assert float(v) == pytest.approx(2.0)  # survivors exact


# -- satellite: narrowed keys on the shuffle wire ------------------------------


def test_key_range_narrows_wire_and_stays_exact():
    rng = np.random.RandomState(3)
    keys = rng.randint(0, 100, 128).astype(np.float32)
    vals = rng.randint(-4, 5, 128).astype(np.float32)
    mask = np.ones(128, np.float32)
    results = {}
    for key_range in (None, 100):
        hm, st = _run("eager", keys, vals, mask, 512, key_range=key_range)
        st = st.finalize()
        results[key_range] = st
        got = {int(k): float(v) for k, v in hm.to_dict().items()}
        assert got == pytest.approx(_dict_oracle(keys, vals, mask))
    wide, narrow = results[None], results[100]
    assert wide.pairs_shipped == narrow.pairs_shipped
    # int32+f32 = 8 B/pair -> int8 key + f32 val = 5 B/pair
    assert wide.shuffle_payload_bytes == wide.pairs_shipped * 8
    assert narrow.shuffle_payload_bytes == narrow.pairs_shipped * 5
    assert "5B" in narrow.collective and "8B" in wide.collective


def test_key_range_16bit_band():
    """A vocab over int8 range narrows to int16 (6 B/pair)."""
    rng = np.random.RandomState(4)
    keys = rng.randint(0, 1000, 64).astype(np.float32)
    vals = np.ones(64, np.float32)
    hm, st = _run(
        "pallas", keys, vals, np.ones(64, np.float32), 4096, key_range=1000
    )
    st = st.finalize()
    assert st.shuffle_payload_bytes == st.pairs_shipped * 6
    got = {int(k): float(v) for k, v in hm.to_dict().items()}
    assert got == pytest.approx(
        _dict_oracle(keys, vals, np.ones(64, np.float32))
    )


# -- satellite: stable bucketing ----------------------------------------------


def test_bucket_by_dest_stable_rank_with_duplicate_destinations():
    """With every pair bound for the SAME destination and a bucket smaller
    than the stream, the kept pairs must be the first-emitted ones in
    emission order — the stable-sort guarantee the rank logic assumes."""
    n, cap = 32, 8
    keys = jnp.full((n,), 5, jnp.int32)  # one key -> one destination
    vals = jnp.arange(n, dtype=jnp.float32)  # emission-order tag
    valid = jnp.ones((n,), bool)
    bkeys, bvals, dropped = bucket_by_dest(keys, vals, valid, 1, cap, 0.0)
    assert int(dropped) == n - cap
    np.testing.assert_array_equal(
        np.asarray(bvals[0]), np.arange(cap, dtype=np.float32)
    )
    # mixed destinations: each bucket keeps ITS first-emitted pairs in order
    keys2 = jnp.asarray(np.arange(n) % 7, jnp.int32)
    bkeys2, bvals2, dropped2 = bucket_by_dest(
        keys2, vals, valid, 4, 4, 0.0
    )
    dests = np.asarray(C.shard_of_key(keys2, 4))
    for dshard in range(4):
        mine = np.asarray(vals)[dests == dshard][:4]
        got = np.asarray(bvals2[dshard])[: len(mine)]
        np.testing.assert_array_equal(got, mine)


# -- program-mode wordcount acceptance ----------------------------------------


@pytest.mark.parametrize("engine", ("eager", "pallas"))
def test_program_mode_wordcount_fusion_counters(engine):
    """10-iteration program-mode wordcount = 1 program compile,
    ceil(10/5) = 2 dispatches, ZERO per-iteration host syncs — and the
    counts are exactly 10x the single-pass oracle."""
    from repro.core.algorithms import wordcount

    rng = np.random.RandomState(0)
    lines = rng.randint(0, 50, (32, 8)).astype(np.int32)
    lines[rng.rand(32, 8) < 0.1] = -1
    ref = collections.Counter(lines[lines >= 0].reshape(-1).tolist())

    sess = BlazeSession()
    res = wordcount(
        lines, engine=engine, mode="program", iters=10, unroll=5,
        session=sess,
    )
    assert res.program_compiles == 1
    assert res.dispatches == 2
    assert res.host_syncs == 0
    assert res.iterations == 10
    got = {int(k): int(v) for k, v in res.counts.to_dict().items()}
    assert got == {k: 10 * v for k, v in ref.items()}
    assert res.counts.total_overflow() == 0


def test_program_vs_per_op_wordcount_dispatch_gap():
    from repro.core.algorithms import wordcount

    lines = np.random.RandomState(1).randint(0, 30, (16, 8)).astype(np.int32)
    per_op = wordcount(
        lines, mode="per_op", iters=10, session=BlazeSession()
    )
    prog = wordcount(
        lines, mode="program", iters=10, unroll=5, session=BlazeSession()
    )
    assert per_op.dispatches == 10 and prog.dispatches == 2
    assert (
        {int(k): int(v) for k, v in per_op.counts.to_dict().items()}
        == {int(k): int(v) for k, v in prog.counts.to_dict().items()}
    )


def test_program_multipass_hash_then_dense():
    """A second fused pass reads the UPDATED hash table as a source —
    multi-pass aggregation without leaving the executable."""
    rng = np.random.RandomState(2)
    lines = rng.randint(0, 30, (16, 8)).astype(np.int32)
    ref = collections.Counter(lines.reshape(-1).tolist())

    from repro.core.algorithms.wordcount import wordcount_mapper

    sess = BlazeSession()
    lines_v = distribute(lines, sess.mesh)
    hm = make_dist_hashmap(sess.mesh, 256, (), jnp.int32, "sum")

    def hist_mapper(k, v, emit):
        emit(jnp.minimum(v, 15), 1)

    def step(ctx, s):
        counts = ctx.map_reduce(
            lines_v, wordcount_mapper, "sum", hm, engine="pallas",
            key_range=30,
        )
        hist = ctx.map_reduce(
            counts, hist_mapper, "sum", jnp.zeros((16,), jnp.int32)
        )
        return {"hist": hist}

    prog = sess.program(step)
    state = prog({"hist": jnp.zeros((16,), jnp.int32)}, 1)
    got = {int(k): int(v) for k, v in prog.hash_result(hm).to_dict().items()}
    assert got == dict(ref)
    hist_ref = collections.Counter(min(c, 15) for c in ref.values())
    got_hist = {
        i: int(v) for i, v in enumerate(np.asarray(state["hist"])) if v
    }
    assert got_hist == dict(hist_ref)
    assert prog.hash_slots == 1 and prog.stats.compiles == 1


# -- lane compaction of the eager local combine --------------------------------

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# name: (host devices, key law, emitted pairs, key range, table capacity per
# shard, reducer).  Every range is below the pairs a shard emits, so the
# combine's output is compacted to it.
_COMPACTION_CASES = {
    "zipf": (1, "zipf", 8192, 2048, 8192, "sum"),
    "uniform": (1, "uniform", 8192, 2048, 8192, "sum"),
    "cell-block": (1, "zipf", 131072, 32768, 131072, "sum"),
    "near-capacity": (1, "uniform", 8192, 1024, 1400, "min"),
    "mesh-zipf": (8, "zipf", 32768, 1024, 1024, "sum"),
    "mesh-near-capacity": (8, "uniform", 32768, 1024, 136, "max"),
}


def _int_mapper(i, row, emit):
    emit(row[0], row[1])


def _compaction_pair(name: str, seed: int = 0) -> dict:
    """One eager ``map_reduce`` of the case's pairs with ``key_range`` and
    one without, on the session's mesh: whether the tables match byte for
    byte, each side's overflow, probe rounds and shuffle lanes, and whether
    the answer is the dict oracle's (survivors exact, every distinct key
    either in the table or counted in ``overflow``)."""
    _, law, n, key_range, cap, reducer = _COMPACTION_CASES[name]
    rng = np.random.RandomState(seed)
    if law == "zipf":  # bounded Zipf, exponent 1.0, as the word-count cell
        p = 1.0 / np.arange(1, key_range + 1)
        keys = rng.choice(key_range, n, p=p / p.sum())
    else:
        keys = rng.randint(0, key_range, n)
    vals = rng.randint(-1000, 1000, n)
    rows = distribute(np.stack([keys, vals], 1).astype(np.int32), SESS.mesh)
    sides = {}
    for kr in (None, key_range):
        hm = make_dist_hashmap(SESS.mesh, cap, (), jnp.int32, reducer)
        out, st = SESS.map_reduce(
            rows, _int_mapper, reducer, hm, engine="eager", key_range=kr,
            return_stats=True,
        )
        st = st.finalize()
        sides[kr] = (out, st)
    (wide, wst), (narrow, nst) = sides[None], sides[key_range]
    same = {
        f: np.asarray(getattr(wide.table, f)).tobytes()
        == np.asarray(getattr(narrow.table, f)).tobytes()
        for f in ("keys", "vals", "overflow")
    }
    want = _dict_oracle(keys, vals, np.ones(n), reducer)
    got = {int(k): int(v) for k, v in narrow.to_dict().items()}
    return {
        "same": same,
        "overflow": [wst.overflow, nst.overflow],
        "rounds": [wst.probe_rounds, nst.probe_rounds],
        "lanes": [wst.shuffle_lanes, nst.shuffle_lanes],
        "n_emit": -(-n // narrow.n_shards),
        "exact": all(want[k] == v for k, v in got.items())
        and len(got) + nst.overflow == len(want),
    }


@pytest.mark.parametrize("name", list(_COMPACTION_CASES))
def test_key_range_compaction_matches_uncompacted(name):
    """With ``key_range`` the eager combine's output is compacted to
    ``min(n_emit, key_range)`` lanes before bucketing; the table (keys,
    values, overflow) and the merge's probe rounds are the ones the
    uncompacted path gives, bit for bit, and the answer is exact."""
    devices, *_, key_range, _, _ = _COMPACTION_CASES[name]
    if devices == 1:
        res = _compaction_pair(name)
    else:
        env = simulated_env(devices)
        env["PYTHONPATH"] = os.pathsep.join(
            [os.path.join(_ROOT, "src"), _ROOT]
        )
        code = (
            "import json, sys\n"
            f"sys.path.insert(0, {os.path.join(_ROOT, 'tests')!r})\n"
            "import test_hash_differential as t\n"
            f"print(json.dumps(t._compaction_pair({name!r})))\n"
        )
        out = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            env=env, timeout=600,
        )
        assert out.returncode == 0, out.stderr[-3000:]
        res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["same"] == {"keys": True, "vals": True, "overflow": True}
    assert res["overflow"][0] == res["overflow"][1]
    assert res["rounds"][0] == res["rounds"][1] > 0
    assert res["lanes"] == [res["n_emit"], key_range]
    assert res["exact"]
    if name.endswith("near-capacity"):
        assert res["overflow"][1] > 0 or res["rounds"][1] > 16


def test_broken_key_range_counts_excess_in_overflow():
    """More distinct keys than ``key_range`` promised: the compacted buffer
    keeps the first ``key_range`` of them (in key order), the rest are
    counted in ``overflow``; none is lost silently."""
    n, n_keys, key_range = 4096, 2000, 500
    rng = np.random.RandomState(12)
    keys = np.concatenate([np.arange(n_keys), rng.randint(0, n_keys, n - n_keys)])
    vals = rng.randint(1, 9, n)
    rows = distribute(np.stack([keys, vals], 1).astype(np.int32), SESS.mesh)
    want = _dict_oracle(keys, vals, np.ones(n))
    for kr, lanes in ((None, n), (key_range, key_range)):
        hm = make_dist_hashmap(SESS.mesh, 4096, (), jnp.int32, "sum")
        out, st = SESS.map_reduce(
            rows, _int_mapper, "sum", hm, engine="eager", key_range=kr,
            return_stats=True,
        )
        st = st.finalize()
        assert st.shuffle_lanes == lanes
        got = {int(k): int(v) for k, v in out.to_dict().items()}
        assert all(want[k] == v for k, v in got.items())
        assert len(got) + st.overflow == n_keys
    assert st.overflow == n_keys - key_range
    assert sorted(got) == list(range(key_range))
    assert st.pairs_shipped == key_range
