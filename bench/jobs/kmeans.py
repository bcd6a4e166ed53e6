"""k-means over resident points: Blaze's small-fixed-key-range path.

Data: ``n_points`` points in ``dim`` dimensions, Gaussian blobs of standard
deviation ``spread`` around ``k`` centres drawn from a normal law of scale
``centre_scale`` (the semantics of ``data/synthetic.cluster_points``), made
on the device in one call and left there, sharded over the mesh.  The
initial centres are ``k`` distinct points drawn from the first
``init_pool``.

Job: the program that ``kmeans(mode="program")`` builds, driven as that
driver drives it: ``run_loop`` over ``steps_per_job`` Lloyd steps with the
driver's convergence test (``tol`` = 0, so every step runs), one more
dispatch for the inertia of the final centres, and the centres and inertia
fetched to the host.  Every job starts from the same initial centres.

Reference: the same Lloyd steps in ``jax.numpy`` at the highest matmul
precision, a block of points at a time.  Compared: the largest gap of a
centre coordinate and the gap of the inertia, each relative to the
reference.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 1 << 22  # points per block of the reference


# -- data ----------------------------------------------------------------------


def blobs(key, n: int, dim: int, k: int, spread: float, scale: float):
    """``[n, dim]`` float32 points around ``k`` centres of scale ``scale``.

    Each point's centre is picked by a one-hot select rather than a gather:
    the TPU lays a gathered ``[n, dim]`` row block out padded to 128 lanes,
    32 times its size."""
    k_c, k_a, k_n = jax.random.split(key, 3)
    centres = jax.random.normal(k_c, (k, dim), jnp.float32) * scale
    assign = jax.random.randint(k_a, (n,), 0, k)
    pick = assign[:, None] == jnp.arange(k)[None, :]
    base = jnp.where(pick[:, :, None], centres[None], 0.0).sum(axis=1)
    return base + jax.random.normal(k_n, (n, dim), jnp.float32) * spread


@dataclasses.dataclass
class Data:
    points: jax.Array  # [n, dim] sharded over the mesh
    init: jax.Array  # [k, dim]


def generate(cfg: dict, traffic: dict, seed: int, mesh) -> Data:
    from jax.sharding import NamedSharding, PartitionSpec as P

    n, k = cfg["n_points"], cfg["k"]
    if n % mesh.size:
        raise ValueError("n_points must split evenly over the chips")
    key = jax.random.key(seed)
    make = jax.jit(
        functools.partial(blobs, n=n, dim=cfg["dim"], k=k,
                          spread=cfg["spread"], scale=cfg["centre_scale"]),
        out_shardings=NamedSharding(mesh, P(mesh.axis_names[0])),
    )
    points = make(jax.random.fold_in(key, 0))
    pick = jax.random.choice(
        jax.random.fold_in(key, 1), cfg["init_pool"], (k,), replace=False
    )
    return Data(points, points[pick])


# -- the program the window drives ---------------------------------------------


class Job:
    def __init__(self, sess, data: Data, cfg: dict, traffic: dict, mesh, span):
        from repro.core import DistVector
        from repro.core.algorithms.kmeans import _program_step

        self._sess = sess
        self._span = span
        self._init = data.init
        self._n = cfg["n_points"]
        self._tol = cfg["tol"]
        self._unroll = traffic["unroll"]
        self.steps_per_job = cfg["steps_per_job"]
        pts = DistVector(data.points, self._n)
        step, self._state0 = _program_step(
            pts, cfg["k"], cfg["dim"], traffic["engine"], "none"
        )
        self._prog = sess.program(step, mesh=mesh)

    def records(self, j: int) -> int:
        return self._n * self.steps_per_job

    def run(self, j: int):
        tol = self._tol
        with self._span("dispatch"):
            state, _ = self._sess.run_loop(
                self._prog, self._state0(self._init),
                cond=lambda s: float(s["move"]) < tol * tol,
                max_iters=self.steps_per_job, unroll=self._unroll,
            )
            probe = self._prog(state, 1)
        with self._span("fetch"):
            centres, inertia = self._sess.host_value(
                (state["centers"], probe["inertia"])
            )
        return np.asarray(centres), float(inertia)


def build(sess, data, cfg, traffic, mesh, span) -> Job:
    return Job(sess, data, cfg, traffic, mesh, span)


# -- reference and comparison ----------------------------------------------------


def _n_blocks(n: int) -> int:
    nb = -(-n // BLOCK)
    while n % nb:
        nb += 1
    return nb


@functools.partial(jax.jit, static_argnames=("nb", "dtype"))
def _pass(points, centres, *, nb: int, dtype):
    """Per-centre sums and counts of the points nearest each centre, and
    the sum of each point's squared distance to its nearest centre."""
    k, dim = centres.shape
    c = centres.astype(dtype)
    # coordinates down the rows, points along the lanes: [nb, dim, block]
    blocks = points.reshape(nb, -1, dim).transpose(0, 2, 1)

    def body(acc, xt):
        xt = xt.astype(dtype)
        d2 = jnp.stack([
            sum((xt[d] - c[j, d]) ** 2 for d in range(dim)) for j in range(k)
        ])  # [k, block]
        near = jnp.argmin(d2, axis=0)
        onehot = (near[None, :] == jnp.arange(k)[:, None]).astype(dtype)
        sums = jnp.einsum("kn,dn->kd", onehot, xt,
                          precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=dtype)
        counts = jnp.sum(onehot, axis=1)
        inertia = jnp.sum(jnp.min(d2, axis=0))
        s, n_, i_ = acc
        return (s + sums, n_ + counts, i_ + inertia), None

    zero = (jnp.zeros((k, dim), dtype), jnp.zeros((k,), dtype),
            jnp.zeros((), dtype))
    (sums, counts, inertia), _ = jax.lax.scan(body, zero, blocks)
    return sums, counts, inertia


def reference(data: Data, cfg: dict, traffic: dict, j: int,
              dtype=jnp.float32):
    """Centres after ``steps_per_job`` Lloyd steps from the initial centres,
    and their inertia, computed in ``dtype``; a centre that loses every
    point keeps its place."""
    with jax.default_matmul_precision("highest"):
        nb = _n_blocks(cfg["n_points"])
        c = data.init.astype(dtype)
        for _ in range(cfg["steps_per_job"]):
            sums, counts, _ = _pass(data.points, c, nb=nb, dtype=dtype)
            c = jnp.where(counts[:, None] > 0,
                          sums / jnp.maximum(counts, 1)[:, None], c)
        _, _, inertia = _pass(data.points, c, nb=nb, dtype=dtype)
    return (np.asarray(jax.device_get(c), np.float64),
            float(jax.device_get(inertia)))


def as_answer(ref):
    return ref


def compare(ans, want) -> dict:
    """``centre_gap``: the largest gap of a centre coordinate over the
    largest coordinate of the reference's centres; ``inertia_gap``: the gap
    of the inertia over the reference's."""
    c, inertia = ans
    c_ref, i_ref = want
    gaps = {"centre_gap": np.abs(np.asarray(c, np.float64) - c_ref).max()
                          / np.abs(c_ref).max(),
            "inertia_gap": abs(inertia - i_ref) / abs(i_ref)}
    # a value that is not a number is as far off as can be
    return {k: float(v) if np.isfinite(v) else float("inf")
            for k, v in gaps.items()}


def check(data: Data, cfg: dict, traffic: dict, answers: dict,
          limits: dict) -> tuple[dict, int]:
    """Every answer of the window against the reference (one run: every
    job starts from the same centres): the worst of each number, and how
    many answers broke a limit."""
    want = reference(data, cfg, traffic, 0)
    worst = {"centre_gap": 0.0, "inertia_gap": 0.0}
    wrong = 0
    for ans in answers.values():
        got = compare(ans, want)
        wrong += any(v > limits[k] for k, v in got.items())
        worst = {k: max(worst[k], got[k]) for k in worst}
    return worst, wrong


# -- the roofline's bytes ----------------------------------------------------------


def passes_per_job(cfg: dict) -> int:
    """Passes over the points in one job: the Lloyd steps and the dispatch
    that reads the inertia of the final centres."""
    return cfg["steps_per_job"] + 1


def step_bytes(cfg: dict) -> float:
    """Least HBM bytes of one Lloyd step: every point read once."""
    return cfg["n_points"] * cfg["dim"] * 4.0


def segment_kernel_bytes(cfg: dict) -> float:
    """Bytes of one pass of the segment kernel, from its operand shapes:
    every pair read once (an int32 centre id and a float32 row of the
    coordinates, the count and the distance) and the ``[k, dim + 2]``
    table written."""
    row = cfg["dim"] + 2
    return cfg["n_points"] * (4.0 + 4.0 * row) + cfg["k"] * row * 4.0
