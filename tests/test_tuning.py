"""Measured-autotuning system tests.

Pins the tune-cache contract: one plan measures exactly once per session and
every later appearance — per-op resubmission, ``run_loop`` programs, served
queries — reuses the winner; a changed ``key_range`` or dtype is a different
plan and re-measures; tuned results stay bit-identical to untuned results
across EVERY candidate config; winners persist to disk and reload.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import cost
from repro.core import containers as C
from repro.core import plan as plan_mod
from repro.core.algorithms.kmeans import _program_step as _kmeans_step
from repro.core.algorithms.wordcount import _program_step as _wc_step
from repro.core.session import BlazeSession
from repro.serve.server import BlazeServer

VOCAB = 40
N_TOKENS = 192


def _tokens(seed=0, n=N_TOKENS, dtype=np.int32):
    return np.random.RandomState(seed).randint(0, VOCAB, size=(n,)).astype(
        dtype
    )


def _wc_mapper(i, tok, emit):
    emit(tok, 1, mask=tok >= 0)


def _hm(sess, dtype=jnp.int32):
    return C.make_dist_hashmap(sess.mesh, 4 * VOCAB, (), dtype, "sum")


def _counts(hm):
    keys, vals = hm.items()
    order = np.argsort(keys, kind="stable")
    return keys[order], vals[order]


def _wc(sess, *, tune=False, key_range=VOCAB, dtype=np.int32):
    lines = C.distribute(_tokens(dtype=dtype), sess.mesh)
    out = sess.map_reduce(
        lines, _wc_mapper, "sum",
        _hm(sess, jnp.dtype(dtype)), key_range=key_range, tune=tune,
    )
    return _counts(out)


# -- measure-once semantics ---------------------------------------------------


def test_map_reduce_measures_once_and_reuses():
    sess = BlazeSession()
    _wc(sess, tune=True)
    first = sess.stats.tune_measurements
    assert first > 0
    assert len(sess.tuning) == 1
    (tk, cfg), = sess.tuning.items()
    assert cfg.source == "measured" and cfg.wall_s is not None
    # resubmission of the same plan: zero new measurements
    _wc(sess, tune=True)
    _wc(sess, tune=False)
    assert sess.stats.tune_measurements == first
    assert len(sess.tuning) == 1


def test_different_key_range_or_dtype_remeasures():
    sess = BlazeSession()
    _wc(sess, tune=True, key_range=VOCAB)
    assert len(sess.tuning) == 1
    _wc(sess, tune=True, key_range=2 * VOCAB)  # different plan hash
    assert len(sess.tuning) == 2
    _wc(sess, tune=True, dtype=np.float32)  # different value dtype
    assert len(sess.tuning) == 3


def test_program_tune_measures_once_across_run_loop_blocks():
    sess = BlazeSession()
    pts = np.random.RandomState(0).randint(-3, 4, size=(256, 4)).astype(
        np.float32
    )
    pts_v = C.distribute(pts, sess.mesh)
    step, state0 = _kmeans_step(pts_v, 8, 4, "auto", "none")
    prog = sess.program(step, mesh=sess.mesh, tune=True)
    c0 = jnp.asarray(pts[:8])
    sess.run_loop(prog, state0(c0), max_iters=6, unroll=2)
    first = sess.stats.tune_measurements
    assert first > 0
    # more blocks, a second tuned program, and an untuned one: no re-measure
    sess.run_loop(prog, state0(c0), max_iters=4)
    prog2 = sess.program(step, mesh=sess.mesh, tune=True)
    sess.run_loop(prog2, state0(c0), max_iters=2)
    assert sess.stats.tune_measurements == first


def _kmeans_program_run(sess, tune):
    pts = np.random.RandomState(3).randint(-4, 5, size=(256, 4)).astype(
        np.float32
    )
    step, state0 = _kmeans_step(C.distribute(pts, sess.mesh), 8, 4, "auto",
                                "none")
    prog = sess.program(step, mesh=sess.mesh, tune=tune)
    state, _ = sess.run_loop(prog, state0(jnp.asarray(pts[:8])), max_iters=5)
    return (np.asarray(state["centers"]),)


def _wordcount_program_run(sess, tune):
    hm = _hm(sess)
    step, state0 = _wc_step(C.distribute(_tokens(), sess.mesh), hm, VOCAB,
                            "auto")
    prog = sess.program(step, mesh=sess.mesh, tune=tune)
    prog(state0, 1)
    return _counts(prog.hash_result(hm))


@pytest.mark.parametrize("run", [_kmeans_program_run, _wordcount_program_run],
                         ids=["kmeans", "wordcount"])
def test_tuned_program_measures_once_and_equals_static_bit_for_bit(run):
    static = run(BlazeSession(), False)
    sess = BlazeSession()
    first = run(sess, True)  # measures, then dispatches the winner
    measured = sess.stats.tune_measurements
    assert measured > 0
    again = run(sess, True)  # a new program of the same plan: no measuring
    assert sess.stats.tune_measurements == measured
    for got in (first, again):
        assert all(np.array_equal(a, b) for a, b in zip(static, got))


def test_tuned_node_annotated_in_plan():
    sess = BlazeSession()
    pts = np.random.RandomState(1).randn(128, 4).astype(np.float32)
    pts_v = C.distribute(pts, sess.mesh)
    step, state0 = _kmeans_step(pts_v, 4, 4, "auto", "none")
    prog = sess.program(step, mesh=sess.mesh, tune=True)
    prog.build(state0(jnp.asarray(pts[:4])))
    nodes = [
        n for n in prog.plan.mapreduce_nodes()
        if not n.dead and n.cse_of is None
    ]
    assert any(n.tuned is not None for n in nodes)
    tuned = next(n for n in nodes if n.tuned is not None)
    assert tuned.tuned.source == "measured"
    assert tuned.engine == tuned.tuned.engine
    rendered = prog.plan.render()
    assert "tuned measured:" in rendered and "cost~" in rendered


# -- bit-equality across every candidate config -------------------------------


def test_dense_candidates_bit_identical():
    pts = np.random.RandomState(2).randint(-4, 5, size=(256, 4)).astype(
        np.float32
    )
    k = 8
    ref = None
    sess = BlazeSession()
    pts_v = C.distribute(pts, sess.mesh)
    step, state0 = _kmeans_step(pts_v, k, 4, "auto", "none")
    state = state0(jnp.asarray(pts[:k]))
    cands = cost.dense_tuning_candidates(k, 6, "sum", jnp.float32)
    assert len(cands) >= 2
    for cfg in cands:
        prog = sess.program(step, mesh=sess.mesh)
        probe = prog.build(state)
        node = next(
            n for n in probe.mapreduce_nodes()
            if not n.dead and n.cse_of is None
        )
        tuned_sess = BlazeSession()
        tuned_sess.tuning.put(node.tune_key, cfg)
        tv = C.distribute(pts, tuned_sess.mesh)
        step_t, state0_t = _kmeans_step(tv, k, 4, "auto", "none")
        prog_t = tuned_sess.program(step_t, mesh=tuned_sess.mesh)
        out, _ = tuned_sess.run_loop(
            prog_t, state0_t(jnp.asarray(pts[:k])), max_iters=5
        )
        got = np.asarray(out["centers"])
        if ref is None:
            ref = got
        else:
            assert np.array_equal(ref, got), cfg


def test_hash_candidates_bit_identical():
    ref = None
    cands = cost.hash_tuning_candidates(
        1, "sum", jnp.int32, key_range=VOCAB
    )
    assert len(cands) >= 2
    # derive the node's tune_key once from an untuned session
    probe_sess = BlazeSession()
    lines = C.distribute(_tokens(), probe_sess.mesh)
    node = plan_mod.build_mapreduce_node(
        idx=0, kind="vector", src="s", source_key=None, mapper=_wc_mapper,
        red=__import__("repro.core.reducers", fromlist=["get_reducer"])
        .get_reducer("sum"),
        target=_hm(probe_sess), engine="auto", wire="none",
        key_range=VOCAB, env=None,
    )
    for cfg in cands:
        sess = BlazeSession()
        sess.tuning.put(node.tune_key, cfg)
        got = _wc(sess, tune=False)
        if ref is None:
            ref = got
        else:
            assert np.array_equal(ref[0], got[0]), cfg
            assert np.array_equal(ref[1], got[1]), cfg


# -- persistence --------------------------------------------------------------


def test_save_load_skips_measurement(tmp_path):
    p = str(tmp_path / "tuning.json")
    sess = BlazeSession(tuning_path=p)
    _wc(sess, tune=True)
    assert sess.stats.tune_measurements > 0
    sess.save_tuning()
    s2 = BlazeSession(tuning_path=p)
    assert len(s2.tuning) == len(sess.tuning)
    _wc(s2, tune=True)
    assert s2.stats.tune_measurements == 0  # winner came off disk
    with pytest.raises(ValueError):
        BlazeSession().save_tuning()  # no path configured anywhere


# -- serving ------------------------------------------------------------------


def test_serve_tuning_stats_conservation():
    rng = np.random.RandomState(0)
    pts = rng.randn(128, 4).astype(np.float32)
    lines = rng.randint(0, VOCAB, size=(128, 1)).astype(np.int32)
    srv = BlazeServer(tune=True)
    srv.register_dataset("points", pts)
    srv.register_dataset("lines", lines, vocab_size=VOCAB)
    srv.start()
    try:
        srv.submit_and_wait(
            "t", "kmeans", {"k": 4, "iters": 2, "engine": "auto"}
        )
        srv.submit_and_wait("t", "wordcount", {"engine": "auto"})
        measured = srv.session.stats.tune_measurements
        assert measured > 0
        # resubmission: plan-cache hit, no re-measure
        srv.submit_and_wait(
            "t", "kmeans", {"k": 4, "iters": 2, "engine": "auto"}
        )
        assert srv.session.stats.tune_measurements == measured
        snap = srv.stats_snapshot()
        t = snap["tuning"]
        assert (
            t["tuned_plans"] + t["fallback_plans"]
            == snap["resident_programs"]
        )
        assert t["tuned_plans"] >= 1
        for info in t["plans"].values():
            for op in info["ops"]:
                assert op["source"] in ("measured", "loaded", "model",
                                        "fallback")
                if op["source"] == "model":
                    assert op["config"] is None
                else:
                    assert op["config"]
        assert t["cache"]["measurements"] == measured
    finally:
        srv.stop()


def test_serve_untuned_plans_are_fallback():
    rng = np.random.RandomState(0)
    srv = BlazeServer()  # tune off: everything rides the model
    srv.register_dataset("points", rng.randn(64, 4).astype(np.float32))
    srv.start()
    try:
        srv.submit_and_wait(
            "t", "kmeans", {"k": 4, "iters": 2, "engine": "auto"}
        )
        snap = srv.stats_snapshot()
        t = snap["tuning"]
        assert t["tuned_plans"] == 0
        assert t["fallback_plans"] == snap["resident_programs"] == 1
        assert srv.session.stats.tune_measurements == 0
    finally:
        srv.stop()
