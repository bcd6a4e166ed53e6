"""PageRank over resident edges: a dense target of a million keys.

Data: a Graph500 Kronecker (R-MAT) graph of ``2**scale`` pages and
``edge_factor`` edges per page.  Each edge takes one bit of its source and
of its destination per level, from one uniform draw ``r``: the source bit is
``r >= a + b``, the destination bit ``a <= r < a + b or r >= a + b + c``
(the rule of ``data/synthetic.rmat_edges``).  As Graph500 asks, the page
labels are then permuted at random.  Made on the device in one call and left
there, with every page's out-degree.

Job: the program that ``pagerank(mode="program")`` builds from its
``_program_step``, driven as that driver drives it: ``run_loop`` over
``steps_per_job`` iterations, ``unroll`` to a dispatch, with the driver's
convergence test (``tol`` = 0, so every iteration runs), and the scores
fetched to the host.  Every job starts from uniform scores.

Reference: the same power iteration in ``jax.numpy``, degrees counted anew
from the edges.  Compared: the largest gap of a page's score relative to
the reference's score of that page.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np


# -- data ----------------------------------------------------------------------


def rmat_bits(key, scale: int, n_edges: int, a: float, b: float, c: float):
    """Unpermuted R-MAT ``(src, dst)`` int32 labels."""
    src = jnp.zeros((n_edges,), jnp.int32)
    dst = jnp.zeros((n_edges,), jnp.int32)
    for bit in range(scale):
        r = jax.random.uniform(jax.random.fold_in(key, bit), (n_edges,))
        src_bit = r >= a + b
        dst_bit = ((r >= a) & (r < a + b)) | (r >= a + b + c)
        src = src | (src_bit.astype(jnp.int32) << bit)
        dst = dst | (dst_bit.astype(jnp.int32) << bit)
    return src, dst


def graph(key, *, scale: int, edge_factor: int, a: float, b: float, c: float):
    """``(edges [E, 2] int32, out-degree [N] int32)`` with permuted labels."""
    n = 1 << scale
    k_bits, k_perm = jax.random.split(key)
    src, dst = rmat_bits(k_bits, scale, n * edge_factor, a, b, c)
    perm = jax.random.permutation(k_perm, n).astype(jnp.int32)
    src, dst = perm[src], perm[dst]
    deg = jnp.zeros((n,), jnp.int32).at[src].add(1)
    return jnp.stack([src, dst], axis=1), deg


@dataclasses.dataclass
class Data:
    edges: jax.Array  # [E, 2] int32, sharded over the mesh
    deg: jax.Array  # [N] int32


def generate(cfg: dict, traffic: dict, seed: int, mesh) -> Data:
    from jax.sharding import NamedSharding, PartitionSpec as P

    n_edges = (1 << cfg["scale"]) * cfg["edge_factor"]
    if n_edges % mesh.size:
        raise ValueError("the edges must split evenly over the chips")
    make = jax.jit(
        functools.partial(graph, scale=cfg["scale"],
                          edge_factor=cfg["edge_factor"], a=cfg["a"],
                          b=cfg["b"], c=cfg["c"]),
        out_shardings=(NamedSharding(mesh, P(mesh.axis_names[0])),
                       NamedSharding(mesh, P())),
    )
    return Data(*make(jax.random.key(seed)))


# -- the program the window drives ---------------------------------------------


class Job:
    def __init__(self, sess, data: Data, cfg: dict, traffic: dict, mesh, span):
        from repro.core import DistVector
        from repro.core.algorithms.pagerank import _program_step

        self._sess = sess
        self._span = span
        self._n = 1 << cfg["scale"]
        self._n_edges = self._n * cfg["edge_factor"]
        self._tol = cfg["tol"]
        self._unroll = traffic["unroll"]
        self.steps_per_job = cfg["steps_per_job"]
        edges = DistVector(data.edges, self._n_edges)
        step, self._state0 = _program_step(
            edges, data.deg, self._n, cfg["damping"], traffic["engine"], "none"
        )
        self._prog = sess.program(step, mesh=mesh)
        self._scores0 = jnp.full((self._n,), 1.0 / self._n, jnp.float32)

    def records(self, j: int) -> int:
        return self._n_edges * self.steps_per_job

    def run(self, j: int) -> np.ndarray:
        tol = self._tol
        with self._span("dispatch"):
            state, _ = self._sess.run_loop(
                self._prog, self._state0(self._scores0),
                cond=lambda s: float(s["delta"]) < tol,
                max_iters=self.steps_per_job, unroll=self._unroll,
            )
        with self._span("fetch"):
            return np.asarray(self._sess.host_value(state["scores"]))


def build(sess, data, cfg, traffic, mesh, span) -> Job:
    return Job(sess, data, cfg, traffic, mesh, span)


# -- reference and comparison ----------------------------------------------------


@functools.partial(jax.jit, static_argnames=("n", "damping", "dtype"))
def _iteration(scores, src, dst, deg, *, n: int, damping: float, dtype):
    share = scores[src] / jnp.maximum(deg[src], 1).astype(dtype)
    incoming = jnp.zeros((n,), dtype).at[dst].add(share)
    sink = jnp.sum(jnp.where(deg == 0, scores, jnp.zeros((), dtype)))
    base = jnp.asarray((1.0 - damping) / n, dtype)
    return base + jnp.asarray(damping, dtype) * (incoming + sink / n)


def reference(data: Data, cfg: dict, traffic: dict, j: int,
              dtype=jnp.float32) -> np.ndarray:
    """Scores after ``steps_per_job`` iterations from uniform scores,
    computed in ``dtype``."""
    n = 1 << cfg["scale"]
    src, dst = data.edges[:, 0], data.edges[:, 1]
    deg = jnp.zeros((n,), jnp.int32).at[src].add(1)
    scores = jnp.full((n,), 1.0 / n, dtype)
    with jax.default_matmul_precision("highest"):
        for _ in range(cfg["steps_per_job"]):
            scores = _iteration(scores, src, dst, deg, n=n,
                                damping=cfg["damping"], dtype=dtype)
    return np.asarray(jax.device_get(scores), np.float64)


def as_answer(ref):
    return ref


def compare(ans: np.ndarray, want: np.ndarray) -> dict:
    """``score_gap``: the largest gap of a page's score over the
    reference's score of that page."""
    ans = np.asarray(ans, np.float64)
    if ans.shape != want.shape or not np.isfinite(ans).all():
        return {"score_gap": float("inf")}
    return {"score_gap": float(np.max(np.abs(ans - want) / want))}


def check(data: Data, cfg: dict, traffic: dict, answers: dict,
          limits: dict) -> tuple[dict, int]:
    """Every answer of the window against the reference (one run: every
    job starts from the same scores): the worst gap, and how many answers
    broke the limit."""
    want = reference(data, cfg, traffic, 0)
    worst, wrong = 0.0, 0
    for ans in answers.values():
        gap = compare(ans, want)["score_gap"]
        wrong += gap > limits["score_gap"]
        worst = max(worst, gap)
    return {"score_gap": worst}, wrong


# -- the roofline's bytes ----------------------------------------------------------


def step_bytes(cfg: dict) -> float:
    """Least HBM bytes of one iteration: every edge read once (two int32
    labels), and per page its score and out-degree read and its new score
    written once."""
    n = 1 << cfg["scale"]
    return n * cfg["edge_factor"] * 8.0 + n * 12.0
