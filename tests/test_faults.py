"""Chaos suite: deterministic fault injection against the supervisors.

Every recovery path PR 9 claims is reproduced here on demand, from seeded
schedules, and held to two laws:

* **bit-equality** — a run that survives injected faults (retry, engine
  degradation, overflow escalation, crash + resume) produces *bit-identical*
  results to the fault-free run.  All fault points fire before the
  executable runs or any carry is written, so a retried dispatch replays
  exactly;
* **conservation** — every injected fault is disposed exactly once:
  ``injected_total == retried + degraded + escalated + fatal``
  (``faults.snapshot()["balanced"]``), across threads (prefetch worker,
  serve dispatcher) and across any seeded schedule.

The acceptance proofs from the issue live here too: mid-stream crash at a
checkpointed epoch resumes bit-equal; hash overflow auto-escalates capacity
along the cost grid to a dict-oracle-exact result; an injected Pallas fault
degrades the node to eager with correct results, visible provenance, and no
executable-cache poisoning (the follow-up identical query is a 0-compile
hit).
"""
from __future__ import annotations

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import faults
from repro.core import containers as C
from repro.core.algorithms.kmeans import kmeans
from repro.core.algorithms.pagerank import pagerank
from repro.core.session import BlazeSession

# Fast supervision for tests: no sleeps, no wall-clock deadline.
FAST = faults.RetryPolicy(attempts=3, backoff_s=0.0, multiplier=1.0,
                          deadline_s=None)


@pytest.fixture(autouse=True)
def _clean_registry():
    """Every test starts and ends with a disarmed registry and a zeroed
    ledger (ignoring any ambient BLAZE_FAULTS)."""
    faults.reset(env=False)
    yield
    faults.reset(env=False)


def _sq_mapper(i, x, emit):
    emit(jnp.asarray(x, jnp.int32) % 8, x)


def _sess(**kw):
    kw.setdefault("retry", FAST)
    return BlazeSession(**kw)


def _assert_balanced(**expect):
    snap = faults.snapshot()
    assert snap["balanced"], snap
    for k, v in expect.items():
        assert snap["dispositions"][k] == v, (k, snap)


# -- registry / rule unit behavior --------------------------------------------


def test_rule_needs_exactly_one_trigger():
    with pytest.raises(ValueError):
        faults.FaultRule("dispatch")
    with pytest.raises(ValueError):
        faults.FaultRule("dispatch", at=1, every=2)
    with pytest.raises(ValueError):
        faults.FaultRule("dispatch", at=0)


def test_retry_policy_validation():
    with pytest.raises(ValueError):
        faults.RetryPolicy(attempts=0)
    with pytest.raises(ValueError):
        faults.RetryPolicy(multiplier=0.5)


def test_env_spec_parsing(monkeypatch):
    monkeypatch.setenv(
        faults.ENV_VAR, "dispatch:at=3;kernel.hash:p=0.1,seed=42,fatal"
    )
    faults.reset()
    snap = faults.snapshot()
    assert snap["armed"] and snap["rules"] == 2
    rules = {r.point: r for r in faults.registry._rules}
    assert rules["dispatch"].at == 3 and not rules["dispatch"].fatal
    assert rules["kernel.hash"].p == 0.1
    assert rules["kernel.hash"].seed == 42 and rules["kernel.hash"].fatal


def test_env_spec_rejects_unknown_knob(monkeypatch):
    monkeypatch.setenv(faults.ENV_VAR, "dispatch:bogus=1")
    with pytest.raises(ValueError):
        faults.reset()
    faults.reset(env=False)


def test_probabilistic_schedule_is_deterministic():
    def schedule():
        faults.reset(env=False)
        faults.configure("dispatch", p=0.3, seed=7)
        fired = []
        for i in range(50):
            try:
                faults.fault_point("dispatch")
            except faults.TransientFault:
                fired.append(i)
        return fired

    a, b = schedule(), schedule()
    assert a == b and len(a) > 0  # replayable, and actually fires


def test_ledger_disposes_each_fault_once():
    faults.configure("dispatch", at=1)
    with pytest.raises(faults.TransientFault) as ei:
        faults.fault_point("dispatch")
    faults.record("retried", ei.value)
    faults.record("fatal", ei.value)  # second disposition: no-op
    faults.record("retried", ValueError("real"))  # non-injected: no-op
    _assert_balanced(retried=1, fatal=0)
    with pytest.raises(ValueError):
        faults.record("vanished", ei.value)


def test_inject_scopes_the_rule():
    with faults.inject("dispatch", every=1):
        with pytest.raises(faults.TransientFault):
            faults.fault_point("dispatch")
    faults.fault_point("dispatch")  # disarmed again — must not raise
    assert faults.snapshot()["injected_total"] == 1


# -- supervised per-op dispatch -----------------------------------------------


def test_transient_dispatch_fault_retries_bit_equal():
    sess = _sess()
    src = sess.distribute(np.arange(64, dtype=np.float32))
    target = jnp.zeros((8,), jnp.float32)
    ref = sess.map_reduce(src, _sq_mapper, "sum", target)
    # hits are only counted while armed, so the next dispatch is hit 1
    faults.configure("dispatch", at=1)
    out = sess.map_reduce(src, _sq_mapper, "sum", target)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    assert sess.stats.retries == 1
    _assert_balanced(retried=1)


def test_retry_budget_exhaustion_is_fatal():
    sess = _sess()
    src = sess.distribute(np.arange(16, dtype=np.float32))
    faults.configure("dispatch", every=1)  # every attempt faults
    with pytest.raises(faults.TransientFault):
        sess.map_reduce(src, _sq_mapper, "sum", jnp.zeros((8,), jnp.float32))
    # attempts=3: two retries, then the third failure is recorded fatal.
    _assert_balanced(retried=2, fatal=1)


def test_fatal_fault_propagates_immediately():
    sess = _sess()
    src = sess.distribute(np.arange(16, dtype=np.float32))
    faults.configure("dispatch", at=1, fatal=True)
    with pytest.raises(faults.FatalFault):
        sess.map_reduce(src, _sq_mapper, "sum", jnp.zeros((8,), jnp.float32))
    assert sess.stats.retries == 0
    _assert_balanced(fatal=1)


def test_unsupervised_session_propagates_raw():
    sess = BlazeSession(retry=None)
    src = sess.distribute(np.arange(16, dtype=np.float32))
    faults.configure("dispatch", at=1)
    with pytest.raises(faults.TransientFault) as ei:
        sess.map_reduce(src, _sq_mapper, "sum", jnp.zeros((8,), jnp.float32))
    faults.record("fatal", ei.value)  # the test is the supervisor here
    _assert_balanced(fatal=1)


# -- engine degradation (acceptance proof c) ----------------------------------


def test_kernel_fault_degrades_to_eager_no_cache_poisoning():
    sess = _sess()
    src = sess.distribute(np.arange(64, dtype=np.float32))
    target = jnp.zeros((8,), jnp.float32)
    ref = sess.map_reduce(src, _sq_mapper, "sum", target)  # eager reference

    faults.configure("kernel.segment", at=1)
    out, st = sess.map_reduce(
        src, _sq_mapper, "sum", target, engine="pallas", return_stats=True
    )
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    assert st.engine == "eager" and st.degraded_engine == "pallas"
    assert sess.stats.degraded_nodes == 1
    _assert_balanced(degraded=1)

    # Follow-up identical query: served from the degraded node's OWN cache
    # entry — zero new compiles, and the provenance is still visible.
    compiles0 = sess.stats.compiles
    out2, st2 = sess.map_reduce(
        src, _sq_mapper, "sum", target, engine="pallas", return_stats=True
    )
    np.testing.assert_array_equal(np.asarray(out2), np.asarray(ref))
    assert sess.stats.compiles == compiles0  # 0-compile follow-up
    assert st2.cache_hits == 1
    assert st2.degraded_engine == "pallas" and st2.engine == "eager"


def test_hash_kernel_fault_degrades_hash_dispatch():
    sess = _sess()
    n = 64
    rows = sess.distribute(
        np.stack([np.arange(n) % 16, np.ones(n)], axis=1).astype(np.float32)
    )

    def kv_mapper(i, row, emit):
        emit(jnp.asarray(row[0], jnp.int32), row[1])

    hm = C.make_dist_hashmap(sess.mesh, 128, reducer="sum")
    faults.configure("kernel.hash", at=1)
    out, st = sess.map_reduce(
        rows, kv_mapper, "sum", hm, engine="pallas", return_stats=True
    )
    assert st.degraded_engine == "pallas" and st.engine == "eager"
    assert out.to_dict() == {k: 4.0 for k in range(16)}
    _assert_balanced(degraded=1)


def _refused_sum():
    """``sum`` whose segment kernel raises as the TPU compiler does when it
    refuses a kernel — a real exception, not an injected fault."""
    import dataclasses

    from repro.core.reducers import SUM

    def refused(*a, **kw):
        raise RuntimeError("Mosaic failed to compile TPU kernel")

    return dataclasses.replace(SUM, pallas_segment=refused)


def test_real_kernel_exception_propagates_per_op():
    sess = _sess()
    src = sess.distribute(np.arange(64, dtype=np.float32))
    with pytest.raises(RuntimeError, match="Mosaic"):
        sess.map_reduce(
            src, _sq_mapper, _refused_sum(), jnp.zeros((8,), jnp.float32),
            engine="pallas",
        )
    assert sess.stats.degraded_nodes == 0 and sess.stats.retries == 0
    _assert_balanced(degraded=0)


def test_real_kernel_exception_propagates_from_program():
    sess = _sess()
    src = sess.distribute(np.arange(64, dtype=np.float32))
    prog = sess.program(_pallas_step(src, _refused_sum()))
    with pytest.raises(RuntimeError, match="Mosaic"):
        sess.run_loop(prog, jnp.ones((1,), jnp.float32), max_iters=2)
    assert sess.stats.degraded_nodes == 0 and sess.stats.retries == 0
    assert prog.stats.dispatches == 0


def _pallas_step(src, reducer="sum"):
    def step(ctx, state):
        def mapper(i, x, emit, env):
            emit(jnp.asarray(x, jnp.int32) % 8, x * env[0])

        s = ctx.map_reduce(
            src, mapper, reducer, jnp.zeros((8,), jnp.float32),
            engine="pallas", env=state,
        )
        return state * 0.5 + s[:1] * 1e-3

    return step


def test_program_degradation_shows_in_explain():
    sess = _sess()
    src = sess.distribute(np.arange(64, dtype=np.float32))
    state0 = jnp.ones((1,), jnp.float32)

    prog = sess.program(_pallas_step(src))
    faults.configure("kernel.segment", at=1)
    out, _info = sess.run_loop(prog, state0, max_iters=4)
    assert sess.stats.degraded_nodes >= 1
    _assert_balanced(degraded=1)
    rendered = sess.explain(prog)
    assert "degraded 'pallas' -> 'eager' (kernel fault)" in rendered
    # The fault fired before the first executable ever ran, so the whole
    # run was eager — bit-equal to an all-eager program of the same step.
    eager_sess = _sess()
    eager_src = eager_sess.distribute(np.arange(64, dtype=np.float32))

    def eager_step(ctx, state):
        def mapper(i, x, emit, env):
            emit(jnp.asarray(x, jnp.int32) % 8, x * env[0])

        s = ctx.map_reduce(
            eager_src, mapper, "sum", jnp.zeros((8,), jnp.float32), env=state
        )
        return state * 0.5 + s[:1] * 1e-3

    ref, _ = eager_sess.run_loop(
        eager_sess.program(eager_step), state0, max_iters=4
    )
    assert np.asarray(out).tobytes() == np.asarray(ref).tobytes()


def test_degraded_program_rebuild_is_cached():
    """After a mid-session degradation, re-dispatching the same program
    compiles nothing new (the eager executable is resident)."""
    sess = _sess()
    src = sess.distribute(np.arange(64, dtype=np.float32))

    state0 = jnp.ones((1,), jnp.float32)
    prog = sess.program(_pallas_step(src))
    faults.configure("kernel.segment", at=1)
    out1, _ = sess.run_loop(prog, state0, max_iters=2)
    compiles0 = sess.stats.program_compiles
    out2, _ = sess.run_loop(prog, state0, max_iters=2)
    assert sess.stats.program_compiles == compiles0
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))


# -- overflow escalation (acceptance proof b) ---------------------------------


def _kv_rows(sess, n):
    return sess.distribute(
        np.stack([np.arange(n), np.ones(n)], axis=1).astype(np.float32)
    )


def _kv_mapper(i, row, emit):
    emit(jnp.asarray(row[0], jnp.int32), row[1])


def test_overflow_escalates_capacity_to_dict_oracle():
    sess = _sess(escalate_overflow=True)
    n = 300  # far beyond 128 slots/shard
    hm = C.make_dist_hashmap(sess.mesh, 128, reducer="sum")
    out, st = sess.map_reduce(
        _kv_rows(sess, n), _kv_mapper, "sum", hm, return_stats=True
    )
    assert out.total_overflow() == 0
    assert st.escalations >= 1
    assert sess.stats.escalations == st.escalations
    # capacity climbed the shared cost grid (powers of two)
    assert out.capacity_per_shard > 128
    assert out.capacity_per_shard & (out.capacity_per_shard - 1) == 0
    assert out.to_dict() == {k: 1.0 for k in range(n)}


def test_escalation_preserves_existing_entries():
    """Escalation regrows the ORIGINAL target: entries merged before the
    overflowing dispatch survive, exactly."""
    sess = _sess(escalate_overflow=True)
    hm = C.make_dist_hashmap(sess.mesh, 128, reducer="sum")
    hm = sess.map_reduce(_kv_rows(sess, 50), _kv_mapper, "sum", hm)
    assert hm.total_overflow() == 0  # first round fits
    out = sess.map_reduce(_kv_rows(sess, 300), _kv_mapper, "sum", hm)
    assert out.total_overflow() == 0
    want = {k: 2.0 for k in range(50)}
    want.update({k: 1.0 for k in range(50, 300)})
    assert out.to_dict() == want


def test_escalation_is_bounded():
    sess = _sess(escalate_overflow=True, max_escalations=1)
    hm = C.make_dist_hashmap(sess.mesh, 128, reducer="sum")
    out, st = sess.map_reduce(
        _kv_rows(sess, 2000), _kv_mapper, "sum", hm, return_stats=True
    )
    # One doubling (128 -> 256) cannot hold 2000 keys: overflow remains,
    # counted, and escalation stopped at the bound.
    assert st.escalations == 1
    assert out.capacity_per_shard == 256
    assert out.total_overflow() > 0


def test_no_escalation_without_opt_in():
    sess = _sess()  # escalate_overflow defaults False
    hm = C.make_dist_hashmap(sess.mesh, 128, reducer="sum")
    out, st = sess.map_reduce(
        _kv_rows(sess, 300), _kv_mapper, "sum", hm, return_stats=True
    )
    assert st.escalations == 0
    assert out.capacity_per_shard == 128
    assert out.total_overflow() > 0  # the counted-drop contract holds


# -- checkpoint/resume (acceptance proof a) -----------------------------------


def _loop_program(sess):
    src = sess.distribute(np.arange(64, dtype=np.float32))

    def step(ctx, state):
        def mapper(i, x, emit, env):
            emit(jnp.asarray(x, jnp.int32) % 8, x * env[0])

        s = ctx.map_reduce(
            src, mapper, "sum", jnp.zeros((8,), jnp.float32), env=state
        )
        return state * 0.9 + s[:1] * 1e-4

    return sess.program(step)


def _stream_program(sess):
    data = np.arange(512, dtype=np.float32).reshape(-1, 2)
    src = sess.chunked(data, 64)

    def step(ctx, state):
        def mapper(i, x, emit, env):
            emit(jnp.asarray(x[0], jnp.int32) % 4, x[1] * env[0])

        s = ctx.map_reduce(
            src, mapper, "sum", jnp.zeros((4,), jnp.float32), env=state
        )
        return state * 0.8 + s[:1] * 1e-5

    return sess.program(step)


def test_run_loop_resume_bit_equal(tmp_path):
    state0 = jnp.ones((1,), jnp.float32)
    s1 = _sess()
    ref, _ = s1.run_loop(_loop_program(s1), state0, max_iters=8, unroll=2)

    ckpt = str(tmp_path / "loop")
    s2 = _sess()
    s2.run_loop(_loop_program(s2), state0, max_iters=4, unroll=2,
                checkpoint=ckpt, checkpoint_every=2)
    s3 = _sess()
    out, info = s3.run_loop(_loop_program(s3), state0, max_iters=8, unroll=2,
                            checkpoint=ckpt, resume=True)
    assert info.resumed_from == 4 and info.iterations == 4
    assert np.asarray(out).tobytes() == np.asarray(ref).tobytes()


def test_run_loop_resume_requires_checkpoint():
    sess = _sess()
    with pytest.raises(ValueError):
        sess.run_loop(_loop_program(sess), jnp.ones((1,), jnp.float32),
                      max_iters=2, resume=True)


def test_mid_stream_crash_resumes_bit_equal(tmp_path):
    """The headline proof: a fatal fault mid-stream kills the run between
    checkpoints; a FRESH session resumes from the checkpointed epoch and
    finishes bit-equal to the uninterrupted run."""
    state0 = jnp.ones((1,), jnp.float32)
    s1 = _sess()
    ref, _ = s1.run_stream(_stream_program(s1), state0, max_epochs=6)

    ckpt = str(tmp_path / "stream")
    s2 = _sess()
    # 256 rows / 64 per block = 4 blocks per epoch; crash on a dispatch
    # inside epoch 4 (after the epoch-3 checkpoint landed).
    faults.configure("dispatch", at=3 * 4 + 2, fatal=True)
    with pytest.raises(faults.FatalFault):
        s2.run_stream(_stream_program(s2), state0, max_epochs=6,
                      checkpoint=ckpt, checkpoint_every=1)
    _assert_balanced(fatal=1)
    faults.reset(env=False)

    s3 = _sess()
    out, info = s3.run_stream(_stream_program(s3), state0, max_epochs=6,
                              checkpoint=ckpt, resume=True)
    assert info.resumed_from == 3
    assert np.asarray(out).tobytes() == np.asarray(ref).tobytes()


def test_resume_with_empty_dir_starts_fresh(tmp_path):
    state0 = jnp.ones((1,), jnp.float32)
    sess = _sess()
    ref, _ = _sess().run_loop(_loop_program(_sess()), state0, max_iters=4)
    out, info = sess.run_loop(
        _loop_program(sess), state0, max_iters=4,
        checkpoint=str(tmp_path / "empty"), resume=True,
    )
    assert info.resumed_from is None and info.iterations == 4
    assert np.asarray(out).tobytes() == np.asarray(ref).tobytes()


def test_checkpoint_write_fault_is_retried(tmp_path):
    state0 = jnp.ones((1,), jnp.float32)
    sess = _sess()
    faults.configure("checkpoint.write", at=1)
    out, _ = sess.run_loop(
        _loop_program(sess), state0, max_iters=4, unroll=2,
        checkpoint=str(tmp_path / "ck"), checkpoint_every=2,
    )
    _assert_balanced(retried=1)
    # and the retried write really landed: a resume run finds position 4
    s2 = _sess()
    _out, info = s2.run_loop(
        _loop_program(s2), state0, max_iters=4, unroll=2,
        checkpoint=str(tmp_path / "ck"), resume=True,
    )
    assert info.resumed_from == 4 and info.iterations == 0


# -- prefetch supervisor -----------------------------------------------------


def test_prefetch_read_fault_retried_in_worker():
    sess = _sess()
    data = np.arange(512, dtype=np.float32)
    cv = sess.chunked(data, 64)
    ref_sess = _sess()
    ref = np.asarray(
        ref_sess.map_reduce(ref_sess.chunked(data, 64), _sq_mapper, "sum",
                            jnp.zeros((8,), jnp.float32))
    )
    faults.configure("prefetch.read", every=3)
    out = sess.map_reduce(cv, _sq_mapper, "sum", jnp.zeros((8,), jnp.float32))
    np.testing.assert_array_equal(np.asarray(out), ref)
    snap = faults.snapshot()
    assert snap["balanced"] and snap["dispositions"]["retried"] >= 1


# -- seeded chaos schedules over real drivers ---------------------------------


def test_chaos_streaming_kmeans_bit_equal():
    rng = np.random.RandomState(3)
    pts = rng.randn(1024, 4).astype(np.float32)
    init = pts[:4].copy()

    def run(session):
        cv = session.chunked(pts, 256)
        return kmeans(cv, 4, init_centers=init, max_iters=6, mode="stream",
                      session=session)

    ref = run(_sess())
    faults.configure("dispatch", p=0.2, seed=11)
    faults.configure("prefetch.read", p=0.1, seed=12)
    got = run(_sess())
    assert np.asarray(got.centers).tobytes() == np.asarray(ref.centers).tobytes()
    snap = faults.snapshot()
    assert snap["balanced"], snap
    assert snap["injected_total"] >= 1  # the schedule really fired
    assert snap["injected_total"] == sum(snap["dispositions"].values())


def test_chaos_pagerank_per_op_bit_equal():
    rng = np.random.RandomState(5)
    edges = rng.randint(0, 64, size=(512, 2)).astype(np.int64)

    def run(session):
        return pagerank(edges, 64, max_iters=8, session=session)

    ref = run(_sess())
    faults.configure("dispatch", p=0.15, seed=21)
    faults.configure("collective", p=0.2, seed=22)
    got = run(_sess())
    assert np.asarray(got.scores).tobytes() == np.asarray(ref.scores).tobytes()
    snap = faults.snapshot()
    assert snap["balanced"] and snap["injected_total"] >= 1


# -- serving under faults ------------------------------------------------------


def _server(**kw):
    from repro.serve import BlazeServer

    sess = BlazeSession(retry=FAST)
    kw.setdefault("max_queue", 64)
    kw.setdefault("per_tenant_inflight", 64)
    return BlazeServer(sess, **kw)


def test_serve_transient_fault_retries_and_reports():
    srv = _server()
    with srv:
        r0, _ = srv.submit_and_wait("t", "pi", {"n_samples": 512, "iters": 1})
        # hits count only while armed: the next dispatch is hit 1
        faults.configure("dispatch", at=1)
        r1, _ = srv.submit_and_wait("t", "pi", {"n_samples": 512, "iters": 1})
        assert r1["pi"] == r0["pi"]
        snap = srv.stats_snapshot()
    rec = snap["recovery"]
    assert rec["retried_batches"] == 1 and rec["balanced"]
    assert rec["dispositions"]["retried"] == 1
    assert snap["completed"] == 2 and snap["failed"] == 0


def test_serve_kernel_fault_degrades_and_keeps_serving():
    srv = _server()
    with srv:
        faults.configure("kernel.segment", at=1)
        r1, _ = srv.submit_and_wait(
            "t", "pi", {"n_samples": 512, "iters": 1, "engine": "pallas"}
        )
        # follow-up identical query: answered from the degraded program,
        # zero new program compiles
        compiles0 = srv.session.stats.program_compiles
        r2, m2 = srv.submit_and_wait(
            "t", "pi", {"n_samples": 512, "iters": 1, "engine": "pallas"}
        )
        assert srv.session.stats.program_compiles == compiles0
        assert m2["cache"] == "hit"
        snap = srv.stats_snapshot()
    assert r1["counts"] is not None and r2["pi"] == r1["pi"]
    rec = snap["recovery"]
    assert rec["degraded_batches"] == 1 and rec["balanced"]
    assert rec["session_degraded_nodes"] == 1
    assert snap["completed"] == 2


def test_serve_shutdown_drains_with_typed_shutdown():
    from repro.serve import BlazeServer  # noqa: F401 — import check

    srv = _server(max_batch=4)
    srv.start()
    srv.pause_dispatch()  # hold the backlog so stop() must drain it
    reqs = [
        srv.submit("t", "pi", {"n_samples": 512, "iters": 1})
        for _ in range(5)
    ]
    srv.stop(drain_timeout=2.0)
    for req in reqs:
        assert req.done.is_set()
        assert req.error is not None and req.error.code == "SHUTDOWN"
    snap = srv.stats.snapshot()
    # conservation after drain: nothing is left queued or unaccounted
    assert snap["queued"] == 0
    assert snap["submitted"] == snap["completed"] + snap["failed"] == 5
    # stop() is idempotent
    srv.stop()
