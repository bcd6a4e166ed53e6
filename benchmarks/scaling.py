"""Node-scaling benchmark — the x-axis of the paper's Figs 4–8.

Runs word count and PageRank on 1/2/4/8 (simulated) devices, each in a fresh
subprocess with ``--xla_force_host_platform_device_count=N`` (the main
process keeps 1 device).  Simulated CPU devices share one socket, so
*wall-clock* does not scale; what the paper's scaling argument rests on is
the per-device work and the wire bytes, which we report:

  eager: shuffle bytes stay ~flat with N (locally-reduced dense partials),
  naive: shuffle bytes grow with emitted pairs — the cross-rack bottleneck
  the paper's §2.3.2 targets.

Usage: PYTHONPATH=src python -m benchmarks.scaling
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

from repro.launch.simulate import simulated_env

_CHILD = """
import json, numpy as np, jax, jax.numpy as jnp
from repro.core import data_mesh, distribute, make_dist_hashmap, map_reduce
from repro.core.algorithms import pagerank
from repro.data.synthetic import rmat_edges, zipf_corpus

mesh = data_mesh()
n_dev = len(jax.devices())
out = {"devices": n_dev}

lines, _ = zipf_corpus(2048, 16, 20000, seed=0)
lv = distribute(lines, mesh)
def m(i, toks, emit): emit(toks, 1, mask=toks >= 0)
for engine in ("eager", "naive"):
    hm = make_dist_hashmap(mesh, 4 * 20000 // n_dev + 512, (), jnp.int32, "sum")
    hm2, st = map_reduce(lv, m, "sum", hm, mesh=mesh, engine=engine, return_stats=True)
    st = st.finalize()
    out[f"wc_{engine}_shipped_pairs"] = int(st.pairs_shipped)
    out[f"wc_{engine}_bytes"] = int(st.shuffle_payload_bytes)

edges = rmat_edges(10, 16, seed=0)
for engine in ("eager", "naive"):
    res = pagerank(edges, 1 << 10, tol=0, max_iters=2, mesh=mesh, engine=engine)
    out[f"pr_{engine}_bytes_per_iter"] = int(res.shuffle_bytes_per_iter)
print(json.dumps(out))
"""


def run_at(n_devices: int) -> dict:
    env = simulated_env(n_devices)
    env.setdefault("PYTHONPATH", "src")
    p = subprocess.run(
        [sys.executable, "-c", _CHILD], capture_output=True, text=True, env=env,
        timeout=900,
    )
    if p.returncode != 0:
        raise RuntimeError(p.stderr[-2000:])
    return json.loads(p.stdout.strip().splitlines()[-1])


def main():
    rows = [run_at(n) for n in (1, 2, 4, 8)]
    os.makedirs("results", exist_ok=True)
    with open("results/scaling.json", "w") as f:
        json.dump(rows, f, indent=1)
    print("devices,wc_eager_bytes,wc_naive_bytes,pr_eager_B/iter,pr_naive_B/iter")
    for r in rows:
        print(
            f"{r['devices']},{r['wc_eager_bytes']},{r['wc_naive_bytes']},"
            f"{r['pr_eager_bytes_per_iter']},{r['pr_naive_bytes_per_iter']}"
        )


if __name__ == "__main__":
    main()
