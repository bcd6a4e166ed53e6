"""PageRank (paper §3.1.2, Fig. 5) — three MapReduce ops per iteration.

Exactly the paper's decomposition:

  MR1  total score of all sinks               (dense [1] target, "sum")
  MR2  new scores from Eq. 1                  (dense [N] target, "sum")
  MR3  max |Δscore| for the convergence test  (dense [1] target, "max")

Links are stored distributedly (DistVector of [E, 2] edges); scores are a
dense array threaded through ``env`` so one compiled executable serves every
iteration.  ``engine=`` accepts ``"eager" | "pallas" | "naive" | "auto"`` —
MR2's contribution scatter is the dynamic-key combine the pallas kernel
accelerates; MR1/MR3 emit static keys and keep the fused fast path under
every engine.  The paper's Eq. 1 writes the damping constant as d = 0.15; the
conventional damping is 0.85 — ``damping`` is a parameter (default 0.85) and
the benchmark reports both conventions.

Two execution modes:

* ``mode="per_op"`` (default) — one dispatch per MapReduce op plus a blocking
  host sync per iteration for the convergence test: 3 dispatches + 1 sync
  per iteration, 3 compiles total.
* ``mode="program"`` — the whole iteration (all three ops + the score update
  glue) is fused by ``session.program`` into ONE executable and driven by
  ``session.run_loop`` with ``unroll`` iterations per dispatch: 1 program
  compile, ``≤ ⌈iters/unroll⌉`` dispatches and host syncs.  With
  ``wire="int8"`` the fused loop carries quantization error-feedback
  residuals across iterations, keeping the power iteration unbiased.
* ``mode="stream"`` — the out-of-core variant: ``edges`` is a
  ``ChunkedDistVector`` (graphs whose edge list exceeds device memory) and
  one power iteration becomes one ``session.run_stream`` epoch.  Each block
  dispatch accumulates its partial incoming-contribution vector; the score
  update and convergence delta fire only on the epoch's last block.  Still 1
  program compile regardless of block count; out-degrees are computed
  host-side from the blocks before streaming starts.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.core import ChunkedDistVector, DistRange, DistVector, distribute
from repro.core.session import BlazeSession, resolve


def sink_mapper(p, emit, env):
    scores, deg = env
    emit(0, jnp.where(deg[p] == 0, scores[p], 0.0))


def contrib_mapper(i, edge, emit, env):
    scores, deg = env
    src, dst = edge[0], edge[1]
    emit(dst, scores[src] / jnp.maximum(deg[src], 1).astype(scores.dtype))


def delta_mapper(p, emit, env):
    old, new = env
    emit(0, jnp.abs(new[p] - old[p]))


@dataclasses.dataclass
class PageRankResult:
    scores: np.ndarray
    iterations: int
    converged: bool
    shuffle_bytes_per_iter: int
    pairs_shipped_per_iter: int
    compiles: int = 0  # map_reduce executables compiled across ALL iterations
    program_compiles: int = 0  # fused-program executables (mode="program")
    dispatches: int = 0  # executable launches across the loop
    host_syncs: int = 0  # blocking host materialisations across the loop
    collectives_per_iter: int = 0  # optimized plan's collectives (program mode)


def _program_step(edges_v, deg, n_pages: int, damping: float, engine: str,
                  wire: str):
    """(step_fn, state builder) for the planned PageRank iteration.

    The optimizer batches the sink-sum and contribution-sum psums into one
    collective (both f32 sums, same wire) — the delta pmax stays separate —
    so the plan reports 2 collectives/iter instead of 3 (``wire="none"``).
    """
    pages = DistRange(0, n_pages, 1)
    d = damping

    def step(ctx, s):
        sc = s["scores"]
        sink = ctx.map_reduce(
            pages, sink_mapper, "sum", jnp.zeros((1,), jnp.float32),
            engine=engine, env=(sc, deg),
        )[0]
        incoming = ctx.map_reduce(
            edges_v, contrib_mapper, "sum",
            jnp.zeros((n_pages,), jnp.float32),
            engine=engine, wire=wire, env=(sc, deg),
        )
        new = (1.0 - d) / n_pages + d * (incoming + sink / n_pages)
        delta = ctx.map_reduce(
            pages, delta_mapper, "max", jnp.zeros((1,), jnp.float32),
            engine=engine, env=(sc, new),
        )[0]
        return {"scores": new, "delta": jnp.asarray(delta)}

    def state0(scores):
        return {"scores": scores, "delta": jnp.asarray(jnp.inf, jnp.float32)}

    return step, state0


def _stream_step(edges_c: ChunkedDistVector, deg, n_pages: int,
                 damping: float, engine: str, wire: str):
    """(step_fn, state builder) for the out-of-core PageRank epoch.

    Per block dispatch: MR2 over the resident edge block accumulates into
    ``acc``; the sink sum (MR1), Eq. 1 update and delta test (MR3) are traced
    every dispatch but only *committed* on the epoch's last block, where
    ``acc`` holds the full incoming vector — the accumulate/finalize-on-
    last-block pattern, one executable for every block of every epoch.
    """
    pages = DistRange(0, n_pages, 1)
    d = damping
    n_blocks = edges_c.n_blocks

    def step(ctx, s):
        sc = s["scores"]
        part = ctx.map_reduce(
            edges_c, contrib_mapper, "sum",
            jnp.zeros((n_pages,), jnp.float32),
            engine=engine, wire=wire, env=(sc, deg),
        )
        acc = s["acc"] + part
        last = s["blk"] == n_blocks - 1
        sink = ctx.map_reduce(
            pages, sink_mapper, "sum", jnp.zeros((1,), jnp.float32),
            engine=engine, env=(sc, deg),
        )[0]
        new = (1.0 - d) / n_pages + d * (acc + sink / n_pages)
        delta = ctx.map_reduce(
            pages, delta_mapper, "max", jnp.zeros((1,), jnp.float32),
            engine=engine, env=(sc, new),
        )[0]
        return {
            "scores": jnp.where(last, new, sc),
            "delta": jnp.where(last, jnp.asarray(delta), s["delta"]),
            "acc": jnp.where(last, jnp.zeros_like(s["acc"]), acc),
            "blk": jnp.where(last, 0, s["blk"] + 1),
        }

    def state0(scores):
        return {
            "scores": scores,
            "delta": jnp.asarray(jnp.inf, jnp.float32),
            "acc": jnp.zeros((n_pages,), jnp.float32),
            "blk": jnp.zeros((), jnp.int32),
        }

    return step, state0


def pagerank(
    edges: np.ndarray,
    n_pages: int,
    *,
    damping: float = 0.85,
    tol: float = 1e-5,
    max_iters: int = 100,
    mesh: Mesh | None = None,
    engine: str = "eager",
    wire: str = "none",
    mode: str = "per_op",
    unroll: int = 1,
    session: BlazeSession | None = None,
) -> PageRankResult:
    if mode not in ("per_op", "program", "stream"):
        raise ValueError(
            f"unknown mode {mode!r}; choose 'per_op', 'program' or 'stream'"
        )
    sess, mesh = resolve(session, mesh)
    if isinstance(edges, ChunkedDistVector):
        if mode == "program":
            raise ValueError(
                "chunked edges need mode='stream' (the out-of-core program "
                "loop) or mode='per_op'"
            )
        edges_v = edges
        # Out-degrees host-side, one block at a time — the edge list itself
        # never needs to be resident.
        deg_np = np.zeros((n_pages,), np.int64)
        for b in range(edges.n_blocks):
            blk = edges.block_host(b)[: edges.block_true_rows(b)]
            deg_np += np.bincount(blk[:, 0], minlength=n_pages)
        deg = jnp.asarray(deg_np.astype(np.int32))
    else:
        edges_v = distribute(edges.astype(np.int32), mesh)
        deg = jnp.asarray(
            np.bincount(edges[:, 0], minlength=n_pages).astype(np.int32)
        )
    pages = DistRange(0, n_pages, 1)
    scores = jnp.full((n_pages,), 1.0 / n_pages, jnp.float32)
    d = damping
    compiles0 = sess.stats.compiles
    dispatches0 = sess.stats.dispatches
    syncs0 = sess.stats.host_syncs

    if mode == "stream":
        if not isinstance(edges_v, ChunkedDistVector):
            raise ValueError(
                "mode='stream' needs ChunkedDistVector edges "
                "(see session.chunked)"
            )
        step, state0 = _stream_step(edges_v, deg, n_pages, d, engine, wire)
        prog = sess.program(step, mesh=mesh)
        state, info = sess.run_stream(
            prog, state0(scores),
            cond=lambda s: float(s["delta"]) < tol,
            max_epochs=max_iters,
        )
        return PageRankResult(
            scores=np.asarray(state["scores"]),
            iterations=info.epochs,
            converged=info.converged,
            shuffle_bytes_per_iter=0,
            pairs_shipped_per_iter=0,
            compiles=sess.stats.compiles - compiles0,
            program_compiles=info.compiles,
            dispatches=sess.stats.dispatches - dispatches0,
            host_syncs=sess.stats.host_syncs - syncs0,
            collectives_per_iter=prog.plan.collectives_per_iter,
        )

    if mode == "program":
        step, state0 = _program_step(edges_v, deg, n_pages, d, engine, wire)
        prog = sess.program(step, mesh=mesh)
        state, info = sess.run_loop(
            prog, state0(scores),
            cond=lambda s: float(s["delta"]) < tol,  # counted by run_loop
            max_iters=max_iters, unroll=unroll,
        )
        return PageRankResult(
            scores=np.asarray(state["scores"]),
            iterations=info.iterations,
            converged=info.converged,
            shuffle_bytes_per_iter=0,  # per-op stats don't exist inside a program
            pairs_shipped_per_iter=0,
            compiles=sess.stats.compiles - compiles0,
            program_compiles=info.compiles,
            dispatches=sess.stats.dispatches - dispatches0,
            host_syncs=sess.stats.host_syncs - syncs0,
            collectives_per_iter=prog.plan.collectives_per_iter,
        )

    it, converged = 0, False
    stats2 = None
    for it in range(1, max_iters + 1):
        sink_total = sess.map_reduce(
            pages, sink_mapper, "sum", jnp.zeros((1,), jnp.float32),
            mesh=mesh, engine=engine, env=(scores, deg),
        )[0]
        incoming, stats2 = sess.map_reduce(
            edges_v, contrib_mapper, "sum", jnp.zeros((n_pages,), jnp.float32),
            mesh=mesh, engine=engine, wire=wire, env=(scores, deg),
            return_stats=True,
        )
        new_scores = (1.0 - d) / n_pages + d * (incoming + sink_total / n_pages)
        delta = sess.map_reduce(
            pages, delta_mapper, "max", jnp.zeros((1,), jnp.float32),
            mesh=mesh, engine=engine, env=(scores, new_scores),
        )[0]
        scores = new_scores
        if float(np.asarray(sess.host_value(delta))) < tol:
            converged = True
            break

    fs = stats2.finalize() if stats2 is not None else None
    return PageRankResult(
        scores=np.asarray(scores),
        iterations=it,
        converged=converged,
        shuffle_bytes_per_iter=fs.shuffle_payload_bytes if fs else 0,
        pairs_shipped_per_iter=fs.pairs_shipped if fs else 0,
        compiles=sess.stats.compiles - compiles0,
        dispatches=sess.stats.dispatches - dispatches0,
        host_syncs=sess.stats.host_syncs - syncs0,
    )


def pagerank_reference(
    edges: np.ndarray, n_pages: int, damping: float = 0.85,
    tol: float = 1e-5, max_iters: int = 100,
) -> np.ndarray:
    """Dense numpy oracle for tests."""
    deg = np.bincount(edges[:, 0], minlength=n_pages)
    scores = np.full(n_pages, 1.0 / n_pages, np.float64)
    for _ in range(max_iters):
        sink_total = scores[deg == 0].sum()
        incoming = np.bincount(
            edges[:, 1], scores[edges[:, 0]] / np.maximum(deg[edges[:, 0]], 1),
            minlength=n_pages,
        )
        new = (1 - damping) / n_pages + damping * (incoming + sink_total / n_pages)
        if np.abs(new - scores).max() < tol:
            scores = new
            break
        scores = new
    return scores.astype(np.float32)
