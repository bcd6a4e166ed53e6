"""Faults planted under the timed path, for the tests that see ``correct``
come out false: the harness runs a cell whole (set-up, window, reference,
comparison) at a tiny size on the CPU with one of these in place."""
from __future__ import annotations

import importlib
import time

import jax.numpy as jnp

from bench import run

# (configuration, traffic, chips) of each kind's cell
CELLS = {
    "wordcount": ("wordcount-text-32k", "stream", 1),
    "kmeans": ("kmeans-paper-100m", "resident", 1),
    "pagerank": ("pagerank-graph500-s20", "resident", 1),
    "wordcount4": ("wordcount-text-32k", "stream-4chip", 4),
}
TINY = {
    "wordcount": {"corpus_lines": 512, "block_lines": 64, "vocab": 512},
    "kmeans": {"n_points": 1 << 14, "init_pool": 256, "steps_per_job": 4},
    "pagerank": {"scale": 8, "steps_per_job": 6},
}
TINY["wordcount4"] = TINY["wordcount"]


def cell(kind: str) -> run.Cell:
    """The kind's cell at the sizes its files give."""
    config, traffic, chips = CELLS[kind]
    entry = {"name": f"{config}.{traffic}", "config": config,
             "traffic": traffic, "chips": chips}
    return run.cell_from(run.load_spec(), entry)


def tiny_cell(kind: str) -> run.Cell:
    c = cell(kind)
    c.config.update(TINY[kind])
    return c


def run_tiny(kind: str, seed: int = 2**31 + 3, trace: bool = False) -> dict:
    return run.run_cell(tiny_cell(kind), seed, 0.0, trace, allow_cpu=True,
                        cache=False, t_start=time.perf_counter())


def freeze_state(monkeypatch) -> None:
    """Every dispatch returns the state it was given, tables unchanged."""
    from repro.core.program import Program

    real = Program.__call__

    def frozen(self, state, n_iters=1, *, stream_blocks=None):
        before = dict(self._hash_state)
        real(self, state, n_iters, stream_blocks=stream_blocks)
        self._hash_state.update(
            {k: v for k, v in before.items() if k in self._hash_state}
        )
        return state

    monkeypatch.setattr(Program, "__call__", frozen)


def half_batch(monkeypatch, kind: str) -> None:
    """The mapper drops every other input record; sums and means are taken
    over the rest."""
    if kind.startswith("wordcount"):
        m = importlib.import_module("repro.core.algorithms.wordcount")

        def mapper(i, tokens, emit):
            emit(tokens, 1, mask=(tokens >= 0) & (i % 2 == 0))

        monkeypatch.setattr(m, "wordcount_mapper", mapper)
    elif kind == "kmeans":
        m = importlib.import_module("repro.core.algorithms.kmeans")

        def mapper(i, x, emit, centers):
            d2 = jnp.sum((centers - x[None, :]) ** 2, axis=1)
            row = jnp.concatenate([x, jnp.ones((1,), x.dtype), jnp.min(d2)[None]])
            emit(jnp.argmin(d2), row, mask=i % 2 == 0)

        monkeypatch.setattr(m, "assign_inertia_mapper", mapper)
    else:
        m = importlib.import_module("repro.core.algorithms.pagerank")

        def mapper(i, edge, emit, env):
            scores, deg = env
            src, dst = edge[0], edge[1]
            share = scores[src] / jnp.maximum(deg[src], 1).astype(scores.dtype)
            emit(dst, share, mask=i % 2 == 0)

        monkeypatch.setattr(m, "contrib_mapper", mapper)


def alter_answer(monkeypatch, kind: str) -> None:
    """One token, or one value of the answer, altered where it is made:
    word 0 counted as word 1; one page's score made 1% larger, or one
    centre coordinate moved by 1% of the largest, in every dispatch's
    state."""
    if kind.startswith("wordcount"):
        m = importlib.import_module("repro.core.algorithms.wordcount")

        def mapper(i, tokens, emit):
            emit(jnp.where(tokens == 0, 1, tokens), 1, mask=tokens >= 0)

        monkeypatch.setattr(m, "wordcount_mapper", mapper)
        return
    from repro.core.program import Program

    real = Program.__call__
    leaf = "centers" if kind == "kmeans" else "scores"

    def altered(self, state, n_iters=1, *, stream_blocks=None):
        out = dict(real(self, state, n_iters, stream_blocks=stream_blocks))
        flat = out[leaf].reshape(-1)
        step = 0.01 * (flat[0] if kind == "pagerank" else jnp.max(jnp.abs(flat)))
        out[leaf] = flat.at[0].add(step).reshape(out[leaf].shape)
        return out

    monkeypatch.setattr(Program, "__call__", altered)


def drop_exchange(monkeypatch) -> None:
    """The all_to_all between chips returns what it was given."""
    from repro.core import mapreduce

    monkeypatch.setattr(mapreduce.RealCollectives, "all_to_all_tiled",
                        lambda self, x: x)
