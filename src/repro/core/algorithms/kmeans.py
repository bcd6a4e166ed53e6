"""K-Means (paper §3.1.3, Fig. 6) — one MapReduce per assignment step.

The mapper assigns a point to its nearest centre and emits
``(centre, [x…, 1])`` — per-centre sums and counts accumulate in one dense
``[K, dim+1]`` target (small fixed key range).  The refinement step is serial,
exactly as in the paper.  Centres are threaded via ``env``.

``engine=`` accepts ``"eager" | "pallas" | "naive" | "auto"``: with pallas
(or auto, since K is small) the per-shard sums-and-counts combine runs
through the segment-reduce kernel's VMEM accumulator.

``mode="program"`` fuses the assignment MapReduce *and* the serial
refinement glue into one executable (``session.program``) and runs
``unroll`` iterations per dispatch device-resident (``session.run_loop``):
1 program compile, ``≤ ⌈iters/unroll⌉`` dispatches/host-syncs, vs one
dispatch + one sync per iteration in ``mode="per_op"``.

In program mode the **inertia rides the assignment pass**: the step's mapper
emits ``(centre, [x…, 1, min_d2])`` into one ``[K, dim+2]`` target, so the
distance computation that picks the centre also yields the point's inertia
contribution — the separate ``inertia_mapper`` pass (which recomputed every
distance) disappears from the plan.  The final inertia w.r.t. the CONVERGED
centres comes from one extra dispatch of the same fused executable (its
centre update is discarded): no per-op executable is ever built, so
10-iteration program k-means reports 0 map_reduce compiles and
``⌈10/unroll⌉ + 1`` dispatches.

``mode="stream"`` is the out-of-core variant: ``points`` is a
``ChunkedDistVector`` and one k-means *iteration* becomes one *epoch* of
``session.run_stream`` — each block dispatch accumulates its partial
``[K, dim+2]`` sums into streamed state, and the refinement step fires only
on the epoch's last block (``jnp.where`` on the block counter).  Still ONE
program compile regardless of block count or iteration count; convergence is
tested once per epoch.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.core import ChunkedDistVector, DistVector, distribute
from repro.core.session import BlazeSession, resolve


def assign_mapper(i, x, emit, centers):
    d2 = jnp.sum((centers - x[None, :]) ** 2, axis=1)
    c = jnp.argmin(d2)
    emit(c, jnp.concatenate([x, jnp.ones((1,), x.dtype)]))


def assign_inertia_mapper(i, x, emit, centers):
    """Program-mode mapper: one distance computation serves both the centre
    assignment AND the point's inertia contribution (``min d²``) — emitted
    together as ``(centre, [x…, 1, min_d2])`` into a ``[K, dim+2]`` target."""
    d2 = jnp.sum((centers - x[None, :]) ** 2, axis=1)
    c = jnp.argmin(d2)
    emit(c, jnp.concatenate([x, jnp.ones((1,), x.dtype), jnp.min(d2)[None]]))


def inertia_mapper(i, x, emit, centers):
    d2 = jnp.sum((centers - x[None, :]) ** 2, axis=1)
    emit(0, jnp.min(d2))


@dataclasses.dataclass
class KMeansResult:
    centers: np.ndarray
    iterations: int
    converged: bool
    inertia: float
    shuffle_bytes_per_iter: int
    compiles: int = 0  # map_reduce executables compiled across ALL iterations
    program_compiles: int = 0  # fused-program executables (mode="program")
    dispatches: int = 0  # executable launches across the loop
    host_syncs: int = 0  # blocking host materialisations across the loop
    collectives_per_iter: int = 0  # optimized plan's collectives (program mode)


def _program_step(pts_v: DistVector, k: int, dim: int, engine: str, wire: str):
    """(step_fn, state builder) for the planned k-means iteration: ONE
    ``[K, dim+2]`` MapReduce (sums | counts | inertia) + the refinement glue."""

    def step(ctx, s):
        c = s["centers"]
        sums = ctx.map_reduce(
            pts_v, assign_inertia_mapper, "sum",
            jnp.zeros((k, dim + 2), jnp.float32),
            engine=engine, wire=wire, env=c,
        )
        counts = jnp.maximum(sums[:, dim:dim + 1], 1.0)
        new_c = sums[:, :dim] / counts  # serial refinement step, fused
        move = jnp.max(jnp.sum((new_c - c) ** 2, axis=1))
        # inertia of the CURRENT centres — the same distances that chose them
        inertia = jnp.sum(sums[:, dim + 1])
        return {"centers": new_c, "move": move, "inertia": inertia}

    def state0(centers):
        return {
            "centers": centers,
            "move": jnp.asarray(jnp.inf, jnp.float32),
            "inertia": jnp.asarray(0.0, jnp.float32),
        }

    return step, state0


def _stream_step(pts_c: ChunkedDistVector, k: int, dim: int, engine: str,
                 wire: str):
    """(step_fn, state builder) for the out-of-core k-means epoch.

    Each dispatch sees ONE resident block: its partial ``[K, dim+2]`` sums
    accumulate into ``acc``; the serial refinement (centre update, move,
    inertia) fires only on the epoch's last block, after which ``acc`` resets
    and the block counter wraps — the accumulate/finalize-on-last-block
    pattern that lets one executable serve every block of every epoch.
    """
    n_blocks = pts_c.n_blocks

    def step(ctx, s):
        c = s["centers"]
        part = ctx.map_reduce(
            pts_c, assign_inertia_mapper, "sum",
            jnp.zeros((k, dim + 2), jnp.float32),
            engine=engine, wire=wire, env=c,
        )
        acc = s["acc"] + part
        last = s["blk"] == n_blocks - 1
        counts = jnp.maximum(acc[:, dim:dim + 1], 1.0)
        new_c = acc[:, :dim] / counts  # refinement — meaningful on last block
        move = jnp.max(jnp.sum((new_c - c) ** 2, axis=1))
        inertia = jnp.sum(acc[:, dim + 1])
        return {
            "centers": jnp.where(last, new_c, c),
            "move": jnp.where(last, move, s["move"]),
            "inertia": jnp.where(last, inertia, s["inertia"]),
            "acc": jnp.where(last, jnp.zeros_like(acc), acc),
            "blk": jnp.where(last, 0, s["blk"] + 1),
        }

    def state0(centers):
        return {
            "centers": centers,
            "move": jnp.asarray(jnp.inf, jnp.float32),
            "inertia": jnp.asarray(0.0, jnp.float32),
            "acc": jnp.zeros((k, dim + 2), jnp.float32),
            "blk": jnp.zeros((), jnp.int32),
        }

    return step, state0


def kmeans(
    points: np.ndarray | DistVector,
    k: int,
    *,
    init_centers: np.ndarray | None = None,
    tol: float = 1e-4,
    max_iters: int = 50,
    mesh: Mesh | None = None,
    engine: str = "eager",
    wire: str = "none",
    mode: str = "per_op",
    unroll: int = 1,
    seed: int = 0,
    session: BlazeSession | None = None,
) -> KMeansResult:
    if mode not in ("per_op", "program", "stream"):
        raise ValueError(
            f"unknown mode {mode!r}; choose 'per_op', 'program' or 'stream'"
        )
    sess, mesh = resolve(session, mesh)
    if isinstance(points, ChunkedDistVector):
        if mode == "program":
            raise ValueError(
                "chunked points need mode='stream' (the out-of-core program "
                "loop) or mode='per_op'"
            )
        pts_v = points
        dim = points.shape_tail[0]
    elif isinstance(points, DistVector):
        pts_v = points
        dim = points.data.shape[1]
    else:
        pts_v = distribute(points.astype(np.float32), mesh)
        dim = points.shape[1]
    if init_centers is None:
        rng = np.random.RandomState(seed)
        if isinstance(pts_v, ChunkedDistVector):
            pool = pts_v.block_host(0)[: pts_v.block_true_rows(0)]
            init_centers = pool[rng.choice(min(len(pool), 4096), k, replace=False)]
        else:
            init_centers = np.asarray(pts_v.data)[
                rng.choice(min(len(pts_v), 4096), k, replace=False)
            ]
    centers = jnp.asarray(init_centers, jnp.float32)
    compiles0 = sess.stats.compiles
    dispatches0 = sess.stats.dispatches
    syncs0 = sess.stats.host_syncs

    if mode == "stream":
        if not isinstance(pts_v, ChunkedDistVector):
            raise ValueError(
                "mode='stream' needs ChunkedDistVector points "
                "(see session.chunked)"
            )
        step, state0 = _stream_step(pts_v, k, dim, engine, wire)
        prog = sess.program(step, mesh=mesh)
        state, info = sess.run_stream(
            prog, state0(centers),
            cond=lambda s: float(s["move"]) < tol * tol,
            max_epochs=max_iters,
        )
        centers = state["centers"]
        # Inertia w.r.t. the FINAL centres: one more epoch of the same
        # executable — its refinement output is discarded, mirroring the
        # in-memory program mode's probe dispatch.
        probe, _ = sess.run_stream(prog, state, max_epochs=1)
        inertia = float(np.asarray(sess.host_value(probe["inertia"])))
        return KMeansResult(
            centers=np.asarray(centers),
            iterations=info.epochs,
            converged=info.converged,
            inertia=inertia,
            shuffle_bytes_per_iter=0,
            compiles=sess.stats.compiles - compiles0,
            program_compiles=info.compiles,
            dispatches=sess.stats.dispatches - dispatches0,
            host_syncs=sess.stats.host_syncs - syncs0,
            collectives_per_iter=prog.plan.collectives_per_iter,
        )

    if mode == "program":
        step, state0 = _program_step(pts_v, k, dim, engine, wire)
        prog = sess.program(step, mesh=mesh)
        state, info = sess.run_loop(
            prog, state0(centers),
            cond=lambda s: float(s["move"]) < tol * tol,
            max_iters=max_iters, unroll=unroll,
        )
        centers = state["centers"]
        # Inertia w.r.t. the FINAL centres: one more dispatch of the same
        # fused executable — its assignment pass IS the inertia pass (the
        # centre update it also computes is discarded).  No per-op
        # executable is ever built for k-means in program mode.
        probe = prog(state, 1)
        inertia = float(np.asarray(sess.host_value(probe["inertia"])))
        return KMeansResult(
            centers=np.asarray(centers),
            iterations=info.iterations,
            converged=info.converged,
            inertia=inertia,
            shuffle_bytes_per_iter=0,
            compiles=sess.stats.compiles - compiles0,
            program_compiles=info.compiles,
            # session delta, not info.dispatches: includes the final inertia
            # probe, so per_op and program rows compare like-for-like
            dispatches=sess.stats.dispatches - dispatches0,
            host_syncs=sess.stats.host_syncs - syncs0,
            collectives_per_iter=prog.plan.collectives_per_iter,
        )

    it, converged, stats = 0, False, None
    for it in range(1, max_iters + 1):
        sums, stats = sess.map_reduce(
            pts_v, assign_mapper, "sum", jnp.zeros((k, dim + 1), jnp.float32),
            mesh=mesh, engine=engine, wire=wire, env=centers, return_stats=True,
        )
        counts = jnp.maximum(sums[:, dim:], 1.0)
        new_centers = sums[:, :dim] / counts  # serial refinement step
        move = float(np.asarray(sess.host_value(
            jnp.max(jnp.sum((new_centers - centers) ** 2, axis=1))
        )))
        centers = new_centers
        if move < tol * tol:
            converged = True
            break

    # Final inertia via one more MapReduce (dense [1] target), materialised
    # through the session so the sync is counted.
    inertia = sess.map_reduce(
        pts_v, inertia_mapper, "sum", jnp.zeros((1,), jnp.float32),
        mesh=mesh, engine=engine, env=centers,
    )[0]
    inertia = float(np.asarray(sess.host_value(inertia)))
    fs = stats.finalize() if stats is not None else None
    return KMeansResult(
        centers=np.asarray(centers),
        iterations=it,
        converged=converged,
        inertia=inertia,
        shuffle_bytes_per_iter=fs.shuffle_payload_bytes if fs else 0,
        compiles=sess.stats.compiles - compiles0,
        dispatches=sess.stats.dispatches - dispatches0,
        host_syncs=sess.stats.host_syncs - syncs0,
    )


def kmeans_reference(
    points: np.ndarray, init_centers: np.ndarray, tol: float = 1e-4,
    max_iters: int = 50,
) -> tuple[np.ndarray, int]:
    """numpy oracle (same init, same convergence rule).  Points are taken a
    chunk at a time, so the oracle's memory stays flat in ``len(points)``."""
    centers = init_centers.astype(np.float64).copy()
    k, dim = centers.shape
    chunk = 1 << 18
    for it in range(1, max_iters + 1):
        sums = np.zeros((k, dim))
        counts = np.zeros(k, np.int64)
        for lo in range(0, len(points), chunk):
            x = points[lo:lo + chunk].astype(np.float64)
            # one centre at a time: no [chunk, K, dim] temporary
            d2 = np.stack([((x - c) ** 2).sum(1) for c in centers], 1)
            assign = d2.argmin(1)
            counts += np.bincount(assign, minlength=k)
            for j in range(dim):
                sums[:, j] += np.bincount(assign, x[:, j], minlength=k)
        new = np.where(
            counts[:, None] > 0, sums / np.maximum(counts, 1)[:, None], centers
        )
        move = ((new - centers) ** 2).sum(1).max()
        centers = new
        if move < tol * tol:
            break
    return centers.astype(np.float32), it
