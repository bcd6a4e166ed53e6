"""BlazeServe example: three tenants querying all six paper algorithms
against one resident server over local HTTP.

The server compiles each distinct plan once; every later query — from any
tenant — rides the resident program cache, and compatible concurrent
queries coalesce into micro-batched dispatches.  The closing /stats
snapshot shows the ledger: compiles vs cache hits, batched dispatches,
p50/p99 latency.

Run:  PYTHONPATH=src python examples/serve_queries.py
"""
import threading

from repro.launch.serve import build_server
from repro.serve import BlazeClient

server = build_server(scale="smoke", max_queue=128, per_tenant=32).start()
print(f"serving {sorted(server.queries)} at {server.url}\n")

QUERIES = [
    ("pi", {"n_samples": 4096, "iters": 2}),
    ("pagerank", {"iters": 10}),
    ("wordcount", {"iters": 1}),
    ("kmeans", {"k": 4, "iters": 5}),
    ("gmm", {"k": 2, "iters": 3}),
    ("knn", {"k": 5, "query": [0.0, 0.0, 0.0, 0.0]}),
]


def describe(query, result):
    if query == "pi":
        return f"pi~{result['pi']:.4f}"
    if query == "pagerank":
        return f"delta={result['delta']:.2e}"
    if query == "wordcount":
        return f"{len(result['keys'])} distinct words"
    if query == "kmeans":
        return f"inertia={result['inertia']:.1f}"
    if query == "gmm":
        return f"ll={result['log_likelihood']:.1f}"
    return f"nearest at d={result['distances'][0]:.3f}"


def tenant(name):
    client = BlazeClient(server.url, tenant=name)
    for query, params in QUERIES:
        result, meta = client.query(query, params)
        print(f"  {name:6s} {query:10s} {describe(query, result):24s} "
              f"cache={meta['cache']:8s} plan={meta['plan_hash']}")


threads = [
    threading.Thread(target=tenant, args=(n,))
    for n in ("alice", "bob", "carol")
]
for t in threads:
    t.start()
for t in threads:
    t.join()

snap = server.stats_snapshot()
print(
    f"\n{snap['completed']} queries, {snap['compiles']} compiles, "
    f"{snap['cache_hits']} cache hits, "
    f"{snap['batched_dispatches']} micro-batched dispatches "
    f"({snap['coalesced_queries']} coalesced); "
    f"p50={snap['p50_ms']:.1f}ms p99={snap['p99_ms']:.1f}ms "
    f"({snap['throughput_qps']:.1f} q/s)"
)
server.stop()
