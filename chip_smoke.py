#!/usr/bin/env python3
"""Blaze on the chip: one pass over the MapReduce main path at full data scale.

Everything runs in ONE process — a chip belongs to one process at a time:

* PageRank over a graph500 R-MAT graph at scale 20 (2^20 pages, 16.8 M
  edges; the paper used 10 M links), ``engine="eager"`` — at 1 M keys no
  VMEM-resident kernel applies;
* k-means over 100 M points around 5 centres in 4 dimensions (the paper's
  point count), two Lloyd steps, ``engine="eager"`` and ``engine="pallas"``
  (the segment-reduce kernel);
* word count over ~52 M Zipf words in 128-lane lines, an eighth of the
  paper's 0.4 B (cut for host generation time), into a hash target with
  ``engine="eager"`` and ``engine="pallas"`` (the hash-aggregation kernel).
  The corpus streams through the program in blocks of 4096 lines
  (``session.chunked``, the out-of-core path): the eager plan sorts every
  pair it counts, and the TPU compiler's time for a sort grows with its
  length (a 1 M-element two-key sort takes it about a minute), so one
  program over all 69 M lanes would spend the run compiling;
* a BlazeServer (``scale="full"``) answering queries of all six kinds over
  local HTTP.

Each job runs as a fused ``BlazeSession`` program (``mode="program"``) and
is checked against its NumPy reference.  One JSON line per phase gives the
wall time, the compile time, the resolved engine of every op and any
``degraded_engine``; the last line is ``{"ok": true, "device": {...}}``.

The run fails (non-zero exit, no result line) when the platform is not a
TPU, when any op was degraded or any dispatch retried, when a pallas
program's executable holds no ``tpu_custom_call``, when a result disagrees
with its reference, or when any phase raises.

  python chip_smoke.py              # one chip: data_mesh(1), all phases
  python chip_smoke.py --chips 4    # the three jobs on data_mesh(4) only;
                                    # the word count also once on
                                    # data_mesh(1), and the tables must be
                                    # bit-equal

On one host the mesh has one node, so the hierarchical-collectives pass has
nothing to rewrite here: ``--chips 4`` exercises the flat data mesh
(sharded sources, psum, the all_to_all shuffle).
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time
import traceback

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

PAPER_WORDS = 400_000_000

# JAX's compile-phase events: lowering to MLIR and the backend compile (the
# trace event is left out: nested jits trace inside their caller's trace).
COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Data scale of each job (the defaults are the full run)."""

    graph_scale: int = 20
    edges_per_node: int = 16
    pagerank_iters: int = 10
    n_points: int = 100_000_000
    dim: int = 4
    k: int = 5
    kmeans_steps: int = 2
    n_lines: int = 540_672  # 132 blocks of 4096 lines
    words_per_line: int = 128
    block_lines: int = 4096
    vocab: int = 512
    serve_scale: str = "full"


class SmokeFailure(AssertionError):
    """A check of the smoke run did not hold."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class CompileClock:
    """Seconds JAX has spent compiling, summed over its compile events."""

    def __init__(self):
        import jax

        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_kw):
        if event in COMPILE_EVENTS:
            self.total += duration


def make_session(mesh):
    """A BlazeSession that keeps the programs it builds, for the report."""
    from repro.core import BlazeSession

    class Session(BlazeSession):
        def __init__(self, mesh):
            super().__init__(mesh)
            self.programs = []

        def program(self, *args, **kwargs):
            prog = super().program(*args, **kwargs)
            self.programs.append(prog)
            return prog

    return Session(mesh)


def on_tpu(mesh) -> bool:
    return mesh.devices.flat[0].platform == "tpu"


def program_report(sess, engine: str, mesh) -> dict:
    """Resolved engine of every live op, and the recovery checks: no op
    degraded, no dispatch retried, a kernel in every pallas executable."""
    ops, kernel_calls = [], []
    for prog in sess.programs:
        nodes = [
            n for n in prog.plan.mapreduce_nodes()
            if not n.dead and n.cse_of is None
        ]
        for n in nodes:
            ops.append({
                "op": f"{n.reducer} -> {n.target_desc}",
                "engine": n.engine,
                "degraded_engine": n.degraded_from,
            })
        if any(n.engine == "pallas" for n in nodes):
            kernel_calls.append("tpu_custom_call" in prog.compiled_text())
    st = sess.stats
    check(all(o["degraded_engine"] is None for o in ops), f"degraded ops: {ops}")
    check(st.degraded_nodes == 0 and st.retries == 0,
          f"degraded_nodes={st.degraded_nodes} retries={st.retries}")
    if engine == "pallas":
        check(any(o["engine"] == "pallas" for o in ops),
              f"no op resolved to pallas: {ops}")
        if on_tpu(mesh):
            check(kernel_calls and all(kernel_calls),
                  "a pallas executable holds no tpu_custom_call")
    return {"ops": ops, "tpu_custom_call": kernel_calls}


def close(name: str, got, want, rtol: float, atol: float) -> dict:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    check(got.shape == want.shape, f"{name}: shape {got.shape} != {want.shape}")
    check(bool(np.isfinite(got).all()), f"{name}: non-finite values")
    err = np.abs(got - want)
    bad = err > atol + rtol * np.abs(want)
    check(not bad.any(), f"{name}: {int(bad.sum())} values off the reference "
          f"(max abs err {err.max():.3e})")
    return {"max_abs_err": float(err.max()),
            "max_rel_err": float((err / np.maximum(np.abs(want), atol)).max())}


def timed(clock, fn):
    c0, t0 = clock.total, time.perf_counter()
    out = fn()
    return out, {"wall_s": time.perf_counter() - t0,
                 "compile_s": clock.total - c0}


# -- phases ---------------------------------------------------------------------


def phase_pagerank(mesh, sz: Sizes, seed: int, clock) -> dict:
    from repro.core.algorithms import pagerank, pagerank_reference
    from repro.data.synthetic import rmat_edges

    n = 1 << sz.graph_scale
    edges = rmat_edges(sz.graph_scale, sz.edges_per_node, seed=seed)
    ref = pagerank_reference(edges, n, tol=0.0, max_iters=sz.pagerank_iters)
    sess = make_session(mesh)
    res, t = timed(clock, lambda: pagerank(
        edges, n, tol=0.0, max_iters=sz.pagerank_iters, mode="program",
        unroll=5, engine="eager", session=sess,
    ))
    check(res.iterations == sz.pagerank_iters, f"ran {res.iterations} iters")
    err = close("pagerank scores", res.scores, ref, rtol=1e-3, atol=1e-3 / n)
    return {"shards": mesh.size, "pages": n, "edges": len(edges),
            "iterations": res.iterations,
            "runs": [{"engine": "eager", **t, **err,
                      **program_report(sess, "eager", mesh)}]}


def phase_kmeans(mesh, sz: Sizes, seed: int, clock) -> dict:
    from repro.core import distribute
    from repro.core.algorithms import kmeans, kmeans_reference
    from repro.data.synthetic import cluster_points

    points, _ = cluster_points(sz.n_points, sz.dim, sz.k, seed=seed)
    rng = np.random.RandomState(seed)
    init = points[rng.choice(min(len(points), 4096), sz.k, replace=False)]
    ref, _ = kmeans_reference(points, init, tol=0.0, max_iters=sz.kmeans_steps)
    pts_v = distribute(points, mesh)
    del points
    runs = []
    for engine in ("eager", "pallas"):
        sess = make_session(mesh)
        res, t = timed(clock, lambda: kmeans(
            pts_v, sz.k, init_centers=init, tol=0.0,
            max_iters=sz.kmeans_steps, mode="program", engine=engine,
            session=sess,
        ))
        check(res.iterations == sz.kmeans_steps, f"ran {res.iterations} steps")
        err = close(f"k-means centres ({engine})", res.centers, ref,
                    rtol=1e-4, atol=1e-4)
        runs.append({"engine": engine, **t, **err,
                     **program_report(sess, engine, mesh)})
    return {"shards": mesh.size, "points": sz.n_points, "dim": sz.dim, "k": sz.k,
            "lloyd_steps": sz.kmeans_steps, "runs": runs}


def _count_words(mesh, lines, sz: Sizes, engine: str, clock):
    from repro.core.algorithms import counts_dict, wordcount

    sess = make_session(mesh)
    blocks = sess.chunked(lines, sz.block_lines)
    res, t = timed(clock, lambda: wordcount(
        blocks, vocab_size=sz.vocab, mode="program", engine=engine,
        session=sess,
    ))
    got = counts_dict(res.counts)
    overflow = res.counts.total_overflow()
    return got, overflow, {
        "engine": engine, "shards": mesh.size, **t,
        **program_report(sess, engine, mesh),
    }


def phase_wordcount(counts, sz: Sizes, seed: int, clock) -> dict:
    """Word count for each ``(mesh, engine)`` in ``counts``; every table must
    equal the corpus's true counts and every other table."""
    from repro.data.synthetic import zipf_corpus

    lines, true_counts = zipf_corpus(
        sz.n_lines, sz.words_per_line, sz.vocab, seed=seed
    )
    want = {w: int(c) for w, c in enumerate(true_counts) if c}
    runs, tables = [], []
    for mesh, engine in counts:
        got, overflow, info = _count_words(mesh, lines, sz, engine, clock)
        check(overflow == 0, f"word count ({engine}) overflowed {overflow}")
        check(got == want, f"word count ({engine}, {mesh.size} shards) "
              "differs from the corpus's true counts")
        tables.append(got)
        runs.append(info)
    check(all(t == tables[0] for t in tables), "word counts differ by mesh")
    return {"lines": sz.n_lines, "words": int(true_counts.sum()),
            "vocab": sz.vocab, "block_lines": sz.block_lines, "runs": runs}


def serve_queries(dim: int) -> list:
    """Two queries of each of the six kinds; ``dim`` is the point width."""
    return [
    ("pi", {"n_samples": 1 << 20, "iters": 2}),
    ("pi", {"n_samples": 1 << 20, "iters": 2, "engine": "pallas"}),
    ("pagerank", {"iters": 10}),
    ("pagerank", {"iters": 10, "engine": "auto"}),
    ("wordcount", {"iters": 1}),
    ("wordcount", {"iters": 1, "engine": "pallas"}),
    ("kmeans", {"k": 8, "iters": 5}),
    ("kmeans", {"k": 8, "iters": 5, "engine": "pallas"}),
    ("gmm", {"k": 2, "iters": 3}),
    ("gmm", {"k": 2, "iters": 3, "engine": "pallas"}),
    ("knn", {"k": 5, "query": [0.0] * dim}),
    ("knn", {"k": 5, "query": [1.0] * dim}),
    ]


def _finite(x) -> bool:
    if isinstance(x, dict):
        return all(_finite(v) for v in x.values())
    if isinstance(x, (list, tuple)):
        return all(_finite(v) for v in x)
    a = np.asarray(x)
    return a.dtype.kind not in "fc" or bool(np.isfinite(a).all())


def phase_serve(sz: Sizes, seed: int, clock) -> dict:
    from repro.launch.serve import build_server
    from repro.serve import BlazeClient

    server = build_server(scale=sz.serve_scale, seed=seed).start()
    queries = serve_queries(server.datasets["points"].value.shape[1])
    try:
        client = BlazeClient(server.url, tenant="smoke")
        answered = []
        for query, params in queries:
            (result, meta), t = timed(
                clock, lambda: client.query(query, params)
            )
            check(_finite(result), f"{query} {params}: non-finite result")
            answered.append({"query": query,
                             "engine": params.get("engine", "eager"),
                             "cache": meta.get("cache"), **t})
        stats = client.stats()
    finally:
        server.stop()
    session = stats["session"]
    check(stats["failed"] == 0, f"server failed {stats['failed']} queries")
    check(stats["completed"] == len(queries),
          f"server completed {stats['completed']} of {len(queries)}")
    check(session["degraded_nodes"] == 0 and session["retries"] == 0,
          f"server session degraded/retried: {session}")
    return {"queries": answered, "completed": stats["completed"],
            "failed": stats["failed"], "kinds": len({q for q, _ in queries})}


def run_phase(name: str, fn, *args) -> bool:
    """Run one phase; print its JSON line; True when it passed."""
    t0 = time.perf_counter()
    try:
        info, ok, error = fn(*args), True, None
    except Exception as e:  # noqa: BLE001 — reported, and fails the run
        traceback.print_exc()
        info, ok, error = {}, False, f"{type(e).__name__}: {e}"
    line = {"phase": name, "ok": ok, "phase_wall_s": time.perf_counter() - t0}
    if error:
        line["error"] = error[:2000]
    line.update(info)
    print(json.dumps(line, default=str), flush=True)
    gc.collect()
    return ok


def run(n_shards: int, sz: Sizes, seed: int, *, serve: bool) -> bool:
    """Every job on ``data_mesh(n_shards)``, and the server when ``serve``;
    True when every phase passed.  On more than one shard the word count
    also runs once on ``data_mesh(1)`` (the fast pallas engine), the table
    the sharded counts must equal bit for bit."""
    from repro.core import data_mesh

    clock = CompileClock()
    mesh = data_mesh(n_shards)
    counts = [(mesh, "eager"), (mesh, "pallas")]
    if n_shards > 1:
        counts.insert(0, (data_mesh(1), "pallas"))
    words = sz.n_lines * sz.words_per_line * 3 // 4
    print(json.dumps({
        "note": f"word count cut to ~{words / 1e6:.1f} M words in "
                f"{sz.n_lines * sz.words_per_line / 1e6:.1f} M lanes, "
                f"{words / PAPER_WORDS:.3f} of the paper's 0.4 B, for host "
                "generation time",
    }), flush=True)
    ok = run_phase("pagerank", phase_pagerank, mesh, sz, seed, clock)
    ok &= run_phase("kmeans", phase_kmeans, mesh, sz, seed, clock)
    ok &= run_phase("wordcount", phase_wordcount, counts, sz, seed, clock)
    if serve:
        ok &= run_phase("serve", phase_serve, sz, seed, clock)
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run the three jobs on data_mesh(4) only")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform!r})",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} device(s)",
              file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache

    print(json.dumps({"compile_cache": enable_compile_cache(),
                      "devices": len(devices)}), flush=True)
    t0 = time.perf_counter()
    ok = run(args.chips, Sizes(), args.seed, serve=args.chips == 1)
    print(json.dumps({"total_wall_s": time.perf_counter() - t0}), flush=True)
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
