"""Unit tests for the MapReduce engine internals."""
import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    EMPTY_KEY,
    DistRange,
    custom_reducer,
    data_mesh,
    distribute,
    foreach,
    get_reducer,
    make_dist_hashmap,
    map_reduce,
    topk,
)
from repro.core.containers import (
    HashTable,
    hash32,
    hashmap_insert,
    hashmap_insert_rounds,
    make_table,
    unique_combine,
)
from repro.core.mapreduce import bucket_by_dest


# -- reducers ----------------------------------------------------------------


@pytest.mark.parametrize("name,fn", [("sum", np.sum), ("min", np.min),
                                     ("max", np.max), ("prod", np.prod)])
def test_builtin_reducer_segment(name, fn):
    red = get_reducer(name)
    rng = np.random.RandomState(0)
    vals = jnp.asarray(rng.rand(64).astype(np.float32) + 0.5)
    ids = jnp.asarray(rng.randint(0, 5, 64))
    out = red.segment(vals, ids, 5)
    for k in range(5):
        ref = fn(np.asarray(vals)[np.asarray(ids) == k])
        assert abs(float(out[k]) - ref) < 1e-3 * max(1, abs(ref))


def test_unknown_reducer_raises():
    with pytest.raises(ValueError):
        get_reducer("bogus")


def test_custom_reducer_segment_and_collective():
    red = custom_reducer(
        "lse", lambda a, b: jnp.logaddexp(a, b),
        lambda dt: jnp.asarray(-jnp.inf, dt),
    )
    vals = jnp.asarray(np.random.RandomState(1).rand(32).astype(np.float32))
    ids = jnp.asarray(np.arange(32) % 3)
    out = red.segment(vals, ids, 3)
    ref = np.full(3, -np.inf)
    for i in range(32):
        ref[i % 3] = np.logaddexp(ref[i % 3], float(vals[i]))
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-5)


# -- unique_combine (eager reduction primitive) -------------------------------


def test_unique_combine_sums_duplicates():
    red = get_reducer("sum")
    keys = jnp.asarray([5, 3, 5, 3, 5, 9], jnp.int32)
    vals = jnp.asarray([1.0, 2.0, 3.0, 4.0, 5.0, 6.0])
    mask = jnp.asarray([True] * 6)
    k, v, valid = unique_combine(keys, vals, mask, red)
    got = {int(kk): float(vv) for kk, vv, m in zip(k, v, valid) if m}
    assert got == {5: 9.0, 3: 6.0, 9: 6.0}


def test_unique_combine_respects_mask():
    red = get_reducer("sum")
    keys = jnp.asarray([1, 1, 2], jnp.int32)
    vals = jnp.asarray([10.0, 20.0, 30.0])
    mask = jnp.asarray([True, False, True])
    k, v, valid = unique_combine(keys, vals, mask, red)
    got = {int(kk): float(vv) for kk, vv, m in zip(k, v, valid) if m}
    assert got == {1: 10.0, 2: 30.0}


# -- hash table ----------------------------------------------------------------


def test_hashmap_insert_basic_and_merge():
    red = get_reducer("sum")
    t = make_table(64, (), jnp.float32, red)
    keys = jnp.asarray([3, 17, 99], jnp.int32)
    vals = jnp.asarray([1.0, 2.0, 3.0])
    t = hashmap_insert(t, keys, vals, jnp.asarray([True] * 3), red)
    t = hashmap_insert(t, keys, vals, jnp.asarray([True, True, False]), red)
    live = {int(k): float(v) for k, v in zip(t.keys, t.vals) if k != EMPTY_KEY}
    assert live == {3: 2.0, 17: 4.0, 99: 3.0}
    assert int(t.overflow) == 0


def test_hashmap_collision_pressure():
    """Many keys into a small table: correct under heavy probing."""
    red = get_reducer("sum")
    n = 48
    t = make_table(128, (), jnp.float32, red)
    keys = jnp.asarray(np.arange(n) * 7919, jnp.int32)
    vals = jnp.ones((n,), jnp.float32)
    t = hashmap_insert(t, keys, vals, jnp.ones(n, bool), red, max_probes=64)
    live = {int(k) for k in t.keys if k != EMPTY_KEY}
    assert int(t.overflow) == 0
    assert live == {int(k) for k in keys}


def test_hashmap_overflow_counted():
    red = get_reducer("sum")
    t = make_table(8, (), jnp.float32, red)  # capacity 8 < 32 keys
    keys = jnp.asarray(np.arange(32), jnp.int32)
    t = hashmap_insert(t, keys, jnp.ones(32), jnp.ones(32, bool), red, max_probes=8)
    assert int(t.overflow) == 32 - int((np.asarray(t.keys) != EMPTY_KEY).sum())
    assert int(t.overflow) > 0


def _fixed_round_insert(table, keys, vals, valid, reducer, max_probes):
    """The probe loop as it was before its early exit: ``max_probes``
    rounds of ``fori_loop``, whether or not any pair is left to place."""
    cap = table.capacity
    h = (hash32(keys) % jnp.uint32(cap)).astype(jnp.int32)

    def round_body(r, state):
        tkeys, tvals, active = state
        slot = ((h + r) % cap).astype(jnp.int32)
        slot_key = jnp.take(tkeys, slot)
        want = active & (slot_key == EMPTY_KEY)
        claim = jnp.full((cap,), EMPTY_KEY, jnp.int32)
        claim = claim.at[jnp.where(want, slot, cap)].max(
            jnp.where(want, keys, EMPTY_KEY), mode="drop"
        )
        tkeys = jnp.where(claim != EMPTY_KEY, claim, tkeys)
        slot_key = jnp.take(tkeys, slot)
        deposit = active & (slot_key == keys)
        cur = jnp.take(tvals, slot, axis=0)
        merged = reducer.combine(cur, vals)
        db = deposit.reshape(deposit.shape + (1,) * (vals.ndim - 1))
        new_at_slot = jnp.where(db, merged, cur)
        tvals = tvals.at[jnp.where(deposit, slot, cap)].set(new_at_slot, mode="drop")
        active = active & ~deposit
        return tkeys, tvals, active

    tkeys, tvals, active = jax.lax.fori_loop(
        0, max_probes, round_body, (table.keys, table.vals, valid)
    )
    overflow = table.overflow + jnp.sum(active).astype(jnp.int32)
    return HashTable(tkeys, tvals, overflow)


# (load factor of the batch, max_probes, reducer, dtype, value shape,
#  pre-filled table, some lanes invalid)
_EARLY_EXIT_CASES = (
    [(load, probes, "sum", "float32", (), False, False)
     for load in (0.05, 0.25, 0.5, 0.9, 1.5) for probes in (1, 16, 64)]
    + [(0.5, 16, red, dtype, shape, True, True)
       for red, dtype in (("sum", "int32"), ("sum", "float32"),
                          ("min", "float32"), ("max", "int32"))
       for shape in ((), (3,))]
    + [(0.9, 64, "min", "int32", (3,), True, False),
       (1.5, 64, "max", "float32", (3,), False, True),
       (0.25, 1, "sum", "int32", (), True, True)]
)


@pytest.mark.parametrize(
    "load,max_probes,red_name,dtype,val_shape,prefill,masked", _EARLY_EXIT_CASES
)
def test_hashmap_insert_early_exit_matches_fixed_rounds(
    load, max_probes, red_name, dtype, val_shape, prefill, masked
):
    """Stopping once every pair is placed gives, bit for bit, the keys,
    values and overflow of running all ``max_probes`` rounds."""
    cap = 256
    red = get_reducer(red_name)
    rng = np.random.RandomState(int(load * 100) + max_probes)

    n, n_pre = int(load * cap), cap // 4
    pool = rng.choice(1 << 20, n + n_pre, replace=False).astype(np.int32)

    def vals_for(k):
        return jnp.asarray(rng.randint(-50, 50, (len(k),) + val_shape), dtype)

    table = make_table(cap, val_shape, jnp.dtype(dtype), red)
    keys = pool[:n]
    if prefill:  # a third of the batch merges into keys already resident
        pkeys = np.concatenate([keys[: n // 3], pool[n:]])[:n_pre]
        table = _fixed_round_insert(
            table, jnp.asarray(pkeys), vals_for(pkeys), jnp.ones(n_pre, bool),
            red, 64,
        )
    keys, vals = jnp.asarray(keys), vals_for(keys)
    valid = jnp.asarray(rng.rand(n) > 0.3) if masked else jnp.ones(n, bool)

    got = jax.jit(
        lambda t, k, v, m: hashmap_insert(t, k, v, m, red, max_probes=max_probes)
    )(table, keys, vals, valid)
    want = jax.jit(
        lambda t, k, v, m: _fixed_round_insert(t, k, v, m, red, max_probes)
    )(table, keys, vals, valid)
    for g, w in zip((got.keys, got.vals, got.overflow),
                    (want.keys, want.vals, want.overflow)):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype and g.shape == w.shape
        assert g.tobytes() == w.tobytes()
    if load > 1:
        assert int(got.overflow) > 0


def test_probe_rounds_empty_and_overflowing_batches():
    red = get_reducer("sum")
    keys = jnp.asarray(np.arange(32), jnp.int32)
    ones = jnp.ones(32, jnp.float32)
    _, rounds = hashmap_insert_rounds(
        make_table(64, (), jnp.float32, red), keys, ones,
        jnp.zeros(32, bool), red, max_probes=16,
    )
    assert int(rounds) == 0
    t, rounds = hashmap_insert_rounds(
        make_table(8, (), jnp.float32, red), keys, ones,
        jnp.ones(32, bool), red, max_probes=8,
    )
    assert int(t.overflow) == 24 and int(rounds) == 8


def test_probe_rounds_in_map_reduce_stats():
    """A word-count-shaped merge: 131,072 received lanes into 131,072 slots
    configure 64 probe rounds, yet keys from a 32,768-word vocabulary (load
    at most 0.25) are all placed within a dozen."""
    words = np.random.RandomState(7).randint(0, 32768, 131072).astype(np.int32)

    def m(i, w, emit):
        emit(w, 1)

    hm = make_dist_hashmap(data_mesh(), 131072, (), jnp.int32, "sum")
    out, stats = map_reduce(
        distribute(words), m, "sum", hm, engine="eager", return_stats=True
    )
    assert isinstance(stats.probe_rounds, jax.Array)  # no host sync yet
    stats = stats.finalize()
    assert isinstance(stats.probe_rounds, int)
    assert 0 < stats.probe_rounds <= 12
    assert stats.overflow == 0
    ids, counts = np.unique(words, return_counts=True)
    assert {int(k): int(v) for k, v in out.to_dict().items()} == dict(
        zip(ids.tolist(), counts.tolist())
    )

    # 40 distinct keys into 8 slots: the merge runs all of its 16 rounds.
    small = make_dist_hashmap(data_mesh(), 8, (), jnp.int32, "sum")
    _, stats = map_reduce(
        distribute(np.arange(40, dtype=np.int32)), m, "sum", small,
        engine="eager", return_stats=True,
    )
    stats = stats.finalize()
    assert stats.probe_rounds == 16 and stats.overflow == 32


# -- bucketing ----------------------------------------------------------------


def test_bucket_by_dest_places_all_pairs():
    keys = jnp.asarray(np.arange(40), jnp.int32)
    vals = jnp.asarray(np.arange(40, dtype=np.float32))
    valid = jnp.ones(40, bool)
    bkeys, bvals, dropped = bucket_by_dest(keys, vals, valid, 4, 20, 0.0)
    assert int(dropped) == 0
    live = np.asarray(bkeys).reshape(-1)
    assert sorted(live[live != EMPTY_KEY]) == list(range(40))
    # every pair landed in the bucket its hash owns
    from repro.core.containers import shard_of_key

    dest = np.asarray(shard_of_key(keys, 4))
    for d in range(4):
        row = np.asarray(bkeys[d])
        for k in row[row != EMPTY_KEY]:
            assert dest[int(np.where(np.asarray(keys) == k)[0][0])] == d


def test_bucket_capacity_drops_counted():
    keys = jnp.zeros(32, jnp.int32)  # all same key → same destination
    vals = jnp.ones(32, jnp.float32)
    bkeys, bvals, dropped = bucket_by_dest(keys, vals, jnp.ones(32, bool), 4, 8, 0.0)
    assert int(dropped) == 32 - 8


# -- engine-level --------------------------------------------------------------


def test_engines_agree_on_hash_target():
    rng = np.random.RandomState(0)
    words = rng.randint(0, 40, 500).astype(np.int32)
    wv = distribute(words)

    def m(i, w, emit):
        emit(w, 1)

    outs = {}
    for engine in ("eager", "naive"):
        hm = make_dist_hashmap(data_mesh(), 512, (), jnp.int32, "sum")
        outs[engine] = map_reduce(wv, m, "sum", hm, engine=engine).to_dict()
    assert {k: int(v) for k, v in outs["eager"].items()} == {
        k: int(v) for k, v in outs["naive"].items()
    }


def test_wire_modes_close_to_exact():
    pts = np.random.RandomState(2).randn(256, 4).astype(np.float32)
    v = distribute(pts)

    def m(i, x, emit):
        emit(i % 8, x)

    t = jnp.zeros((8, 4), jnp.float32)
    exact = np.asarray(map_reduce(v, m, "sum", t))
    for wire, tol in [("bf16", 2e-2), ("int8", 2e-2)]:
        got = np.asarray(map_reduce(v, m, "sum", t, wire=wire))
        denom = np.abs(exact).max()
        assert np.abs(got - exact).max() / denom < tol, wire


def test_foreach_env_and_cache_reuse():
    from repro.core.containers import _FOREACH_CACHE

    v = distribute(np.arange(16, dtype=np.float32))
    n0 = len(_FOREACH_CACHE)

    def f(x, env):
        return x * env

    for scale in (2.0, 3.0, 4.0):
        v2 = foreach(v, f, env=jnp.asarray(scale))
    assert len(_FOREACH_CACHE) == n0 + 1
    np.testing.assert_allclose(np.asarray(v2.data)[:16], np.arange(16) * 4.0)


def test_distrange_source():
    def m(v, emit):
        emit(0, v)

    out = map_reduce(DistRange(0, 100, 1), m, "sum", jnp.zeros((1,), jnp.int32))
    assert int(out[0]) == sum(range(100))


def test_emit_batch_with_mask():
    lines = np.asarray([[1, 2, -1], [3, -1, -1]], np.int32)
    v = distribute(lines)

    def m(i, toks, emit):
        emit(toks, 1, mask=toks >= 0)

    out = map_reduce(v, m, "sum", jnp.zeros((8,), jnp.int32))
    assert [int(x) for x in out[:4]] == [0, 1, 1, 1]


# -- unique_combine sentinel boundaries ---------------------------------------
# The sort used to push masked slots to INT32_MAX, conflating them with
# genuine INT32_MAX keys and dropping genuine EMPTY_KEY keys; the mask now
# rides through the sort (lexsort on (key, liveness)) so every int32 key is a
# legal user key.

INT32_MAX = np.iinfo(np.int32).max


def _combine_oracle(keys, vals, mask):
    want: dict = {}
    for k, v, m in zip(keys, vals, mask):
        if m:
            want[int(k)] = want.get(int(k), 0.0) + float(v)
    return want


def _combine_got(keys, vals, mask):
    red = get_reducer("sum")
    k, v, valid = unique_combine(
        jnp.asarray(keys, jnp.int32), jnp.asarray(vals, jnp.float32),
        jnp.asarray(mask, bool), red,
    )
    return {int(a): float(b) for a, b, m in zip(k, v, valid) if m}


@pytest.mark.parametrize(
    "keys,mask",
    [
        # genuine INT32_MAX keys next to masked slots
        ([INT32_MAX, 7, INT32_MAX, 7], [True, True, False, True]),
        # genuine EMPTY_KEY (INT32_MIN) keys must come out valid
        ([EMPTY_KEY, EMPTY_KEY, 3], [True, True, True]),
        # masked slot whose key VALUE collides with a live key
        ([5, 5, 5], [True, False, True]),
        # all masked
        ([1, 2, 3], [False, False, False]),
        # masked INT32_MAX only — must produce nothing
        ([INT32_MAX, 2], [False, True]),
        # both sentinels live at once
        ([EMPTY_KEY, INT32_MAX, EMPTY_KEY, INT32_MAX],
         [True, True, True, False]),
    ],
)
def test_unique_combine_boundary_keys_match_dict_oracle(keys, mask):
    vals = [float(i + 1) for i in range(len(keys))]
    assert _combine_got(keys, vals, mask) == _combine_oracle(keys, vals, mask)


def test_unique_combine_boundary_fuzz():
    rng = np.random.RandomState(11)
    pool = np.asarray(
        [EMPTY_KEY, EMPTY_KEY + 1, -1, 0, 1, INT32_MAX - 1, INT32_MAX],
        np.int64,
    )
    for _ in range(25):
        n = rng.randint(1, 64)
        keys = pool[rng.randint(0, len(pool), n)]
        vals = rng.randint(0, 100, n).astype(np.float64)  # exact in f32
        mask = rng.rand(n) < 0.7
        got = _combine_got(keys, vals, mask)
        assert got == _combine_oracle(keys, vals, mask)
