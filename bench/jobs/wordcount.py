"""Word count over a streamed corpus: Blaze's hash-target path.

Data: a corpus of ``corpus_lines`` lines of ``lanes`` token ids, each line
filled to a length drawn uniformly from ``[min_words, lanes]`` and padded
with -1.  Words are drawn from a bounded Zipf law (exponent ``zipf_s`` over
``vocab`` words) by inverse CDF, on the device in one call, then kept on the
host in blocks of ``block_lines`` lines: the out-of-core layout that
``session.chunked`` streams.

Job: the program that ``wordcount(mode="program")`` builds, driven by
``session.run_stream`` over ``blocks_per_job`` blocks, its table fetched to
the host.  Job ``j`` reads the ``j``-th run of ``blocks_per_job`` blocks,
wrapping round the corpus.  The program and its source are built once; each
job resets the table and points the source at its blocks, so the window
compiles nothing.

Reference: the count of every word of the job's blocks, one block at a time
with ``jax.numpy``, independent of ``map_reduce``.  The comparison is exact:
every word's count, no key twice, nothing overflowed.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

EMPTY = -1  # pad token
FREE_SLOT = np.iinfo(np.int32).min  # key of a free slot in the answer's table


# -- data ----------------------------------------------------------------------


def zipf_cdf(vocab: int, s: float) -> jax.Array:
    """CDF of the bounded Zipf law over ranks ``1..vocab``; the last entry
    is exactly 1 so an inverse-CDF draw never falls off the end."""
    w = jnp.arange(1, vocab + 1, dtype=jnp.float32) ** -s
    cdf = jnp.cumsum(w) / jnp.sum(w)
    return cdf.at[-1].set(1.0)


@functools.partial(
    jax.jit, static_argnames=("n_lines", "lanes", "vocab", "min_words", "s")
)
def corpus(key, *, n_lines: int, lanes: int, vocab: int, min_words: int,
           s: float) -> jax.Array:
    """``[n_lines, lanes]`` int32 token ids, -1 past each line's length."""
    k_word, k_len = jax.random.split(key)
    u = jax.random.uniform(k_word, (n_lines, lanes), jnp.float32)
    ids = jnp.searchsorted(zipf_cdf(vocab, s), u, side="right")
    ids = jnp.minimum(ids, vocab - 1).astype(jnp.int32)
    lens = jax.random.randint(k_len, (n_lines, 1), min_words, lanes + 1)
    live = jnp.arange(lanes, dtype=jnp.int32)[None, :] < lens
    return jnp.where(live, ids, EMPTY)


@dataclasses.dataclass
class Data:
    blocks: list  # host blocks [block_lines, lanes] int32
    words: np.ndarray  # live words per block


def generate(cfg: dict, traffic: dict, seed: int, mesh) -> Data:
    bl = cfg["block_lines"]
    if cfg["corpus_lines"] % (bl * traffic["blocks_per_job"]):
        raise ValueError("corpus_lines must be whole jobs of whole blocks")
    lines = corpus(
        jax.random.key(seed), n_lines=cfg["corpus_lines"], lanes=cfg["lanes"],
        vocab=cfg["vocab"], min_words=cfg["min_words"], s=cfg["zipf_s"],
    )
    words = jnp.sum(lines.reshape(-1, bl * cfg["lanes"]) >= 0, axis=1)
    host = np.asarray(jax.device_get(lines))
    blocks = [host[i:i + bl] for i in range(0, len(host), bl)]
    return Data(blocks, np.asarray(jax.device_get(words), np.int64))


# -- the program the window drives ---------------------------------------------


class CorpusWindow:
    """The block provider of the job's chunked source: ``n_blocks``
    consecutive corpus blocks from ``first``, each read inside a ``feed``
    span (it runs on the program's prefetch thread)."""

    def __init__(self, blocks: list, n_blocks: int, span):
        self._blocks = blocks
        self._span = span
        self.n_blocks = n_blocks
        self.first = 0
        self.block_shape = blocks[0].shape
        self.dtype = blocks[0].dtype

    def get(self, i: int) -> np.ndarray:
        with self._span("feed"):
            return self._blocks[self.first + i]


@dataclasses.dataclass
class Answer:
    keys: np.ndarray  # [shards, capacity]
    vals: np.ndarray
    overflow: np.ndarray  # [shards]


class Job:
    def __init__(self, sess, data: Data, cfg: dict, traffic: dict, mesh, span):
        from repro.core import ChunkedDistVector, make_dist_hashmap
        from repro.core.algorithms.wordcount import _program_step

        self._sess = sess
        self._data = data
        self._span = span
        self.per_job = traffic["blocks_per_job"]
        self.n_jobs = len(data.blocks) // self.per_job
        self.steps_per_job = self.per_job
        self._window = CorpusWindow(data.blocks, self.per_job, span)
        bl = cfg["block_lines"]
        source = ChunkedDistVector(self._window, self.per_job * bl, bl, mesh)
        # wordcount()'s own table: capacity 4 x vocab per shard
        self._hm = make_dist_hashmap(
            mesh, max(64, 4 * cfg["vocab"]), (), jnp.int32, "sum"
        )
        step, self._state = _program_step(
            source, self._hm, cfg["vocab"], traffic["engine"]
        )
        self._prog = sess.program(step, mesh=mesh)

    def first_block(self, j: int) -> int:
        return (j % self.n_jobs) * self.per_job

    def records(self, j: int) -> int:
        b = self.first_block(j)
        return int(self._data.words[b:b + self.per_job].sum())

    def run(self, j: int) -> Answer:
        self._window.first = self.first_block(j)
        self._prog.reset_carry()
        with self._span("dispatch"):
            self._sess.run_stream(self._prog, self._state, max_epochs=1)
        t = self._prog.hash_result(self._hm).table
        with self._span("fetch"):
            keys, vals, ovf = self._sess.host_value((t.keys, t.vals, t.overflow))
        return Answer(np.asarray(keys), np.asarray(vals), np.asarray(ovf))


def build(sess, data, cfg, traffic, mesh, span) -> Job:
    return Job(sess, data, cfg, traffic, mesh, span)


# -- reference and comparison ----------------------------------------------------


@functools.partial(jax.jit, static_argnames=("vocab", "dtype"))
def _block_counts(block, *, vocab: int, dtype) -> jax.Array:
    ids = jnp.where(block >= 0, block, vocab).reshape(-1)
    return jnp.zeros((vocab + 1,), dtype).at[ids].add(jnp.ones((), dtype))[:vocab]


def reference(data: Data, cfg: dict, traffic: dict, j: int,
              dtype=jnp.int32) -> np.ndarray:
    """Count of every word of job ``j``'s blocks, accumulated in ``dtype``."""
    per = traffic["blocks_per_job"]
    first = (j % (len(data.blocks) // per)) * per
    total = jnp.zeros((cfg["vocab"],), dtype)
    for b in range(first, first + per):
        total = total + _block_counts(
            jnp.asarray(data.blocks[b]), vocab=cfg["vocab"], dtype=dtype
        )
    return np.asarray(jax.device_get(total)).astype(np.int64)


def as_answer(counts: np.ndarray) -> Answer:
    """A reference's counts in the form of the program's answer."""
    live = np.flatnonzero(counts)
    return Answer(live[None].astype(np.int32), counts[live][None],
                  np.zeros((1,), np.int32))


def compare(ans: Answer, want: np.ndarray) -> dict:
    """``count_mismatch``: words whose count differs from the reference,
    plus table entries beyond the first for one key and keys outside the
    vocabulary; ``overflow``: pairs the table dropped."""
    keys = ans.keys.reshape(-1)
    vals = ans.vals.reshape(-1)
    live = keys != FREE_SLOT
    keys, vals = keys[live].astype(np.int64), vals[live].astype(np.int64)
    vocab = len(want)
    inside = (keys >= 0) & (keys < vocab)
    outside = int(np.sum(~inside))
    keys, vals = keys[inside], vals[inside]
    extra = len(keys) - len(np.unique(keys))
    got = np.zeros(vocab, np.int64)
    np.add.at(got, keys, vals)
    return {"count_mismatch": int(np.sum(got != want)) + extra + outside,
            "overflow": int(np.sum(ans.overflow))}


def check(data: Data, cfg: dict, traffic: dict, answers: dict,
          limits: dict) -> tuple[dict, int]:
    """Every answer of the window against its reference: the worst of each
    number, and how many answers broke a limit."""
    worst = {"count_mismatch": 0, "overflow": 0}
    wrong = 0
    for j, ans in answers.items():
        got = compare(ans, reference(data, cfg, traffic, j))
        wrong += any(v > limits[k] for k, v in got.items())
        worst = {k: max(worst[k], got[k]) for k in worst}
    return worst, wrong
