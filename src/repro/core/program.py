"""Fused iteration programs: a whole iteration as ONE optimized executable.

The paper's iterative data-mining wins (PageRank, k-means, GMM/EM) come from
keeping the hot loop resident.  ``BlazeSession`` already makes iteration
*N > 1* compile-free, but a driver written as per-op ``map_reduce`` calls
still pays, per iteration, one executable **dispatch** per op (3–4 for the
paper's algorithms) plus a blocking **host sync** for the convergence test
(``float(delta)``).  Per Li (arXiv:1811.04875), exactly this dispatch/sync
overhead is what separates in-memory MapReduce from MPI/OpenMP on iterative
workloads — and BSP supersteps (Pace, arXiv:1203.2081) are the classical fix:
batch the whole superstep, synchronise once.

This module is that fix on SPMD JAX, built around an explicit logical plan
(``repro.core.plan``) since PR 5:

* **Discovery builds a ``Plan``.** ``step_fn`` runs once under
  ``jax.eval_shape`` with shape-faithful collective stand-ins
  (``AbstractCollectives``).  Instead of consuming the trace inline, the
  context records every ``ctx.map_reduce`` / ``ctx.foreach`` / ``ctx.topk``
  call as a plan node — sources, reducers, wire formats, residual and
  hash-state edges — and the optimizer passes run on that plan:

  - *resolve-engines*: each node gets its own resolved engine
    (``repro.core.plan.resolve_engine``), so one program can mix
    pallas-dense, pallas-hash and eager ops;
  - *batch-collectives*: dense results come back as **lazy plan values**
    (``PlanValue``).  The collective is deferred until the step function
    actually consumes the result; everything pending at that moment with the
    same (reducer, wire, dtype) is concatenated and reduced in ONE
    collective.  GMM's EM round drops from 4 psums to 2 this way — asserted
    via ``Plan.collectives_per_iter``;
  - *cse*: a node identical to an earlier one (same source, mapper,
    reducer, target, engine, wire, env) reuses its result instead of
    recomputing and re-reducing;
  - *prune-dead-sources*: nodes whose results are provably never consumed
    (their lazy value is never forced and not part of the returned state)
    are dropped, and sources referenced only by dropped nodes are never
    shipped into the executable.

* **Execution lowers the plan.** One ``shard_map`` whose body binds
  ``RealCollectives``, maps each *live* source to its shard-local operands,
  and runs ``fori_loop(0, n_iters, step)`` with the user state (replicated)
  plus per-shard feedback residuals and hash tables as carry.  ``jax.jit``
  around it makes the whole block a single dispatch.  The execution context
  replays the same step function against the plan: pruned nodes are skipped,
  CSE'd nodes reuse results, and pending partials flush through the same
  batched collectives the plan recorded.

``session.explain(program)`` renders the optimized plan Spark-EXPLAIN-style;
golden snapshots for the paper's six algorithms live in ``tests/goldens/``.

Iteration-varying values live in ``state``; distributed inputs (the edge
list, the point set) are read through the captured source containers and
enter as sharded operands.  Per-iteration *sharded* intermediates (GMM's
densities/memberships) stay on-shard as ``LocalVector``s produced by
``ctx.foreach`` — they never cross the wire and never leave the executable.

Hash targets (``DistHashMap``) are per-shard state, while the user state
pytree is replicated — so their tables are threaded through the fused loop
the same way int8 error-feedback residuals are: the plan records each target
(keyed by the identity of its backing buffers), the executable takes the
per-shard ``HashTable`` arrays as sharded operands, carries them through
the ``fori_loop``, and returns them updated; ``Program`` keeps the returned
tables across dispatches and ``program.hash_result(hm)`` materialises the
accumulated ``DistHashMap``.  Inside the step, ``ctx.map_reduce`` on a hash
target returns a ``LocalHashMap`` — this shard's updated table, usable as a
source for later ops in the same iteration (multi-pass aggregation without
leaving the executable).
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.compat import shard_map
from repro.core import containers as C
from repro.core import faults
from repro.core import mapreduce as _mr
from repro.core import plan as plan_mod
from repro.core import tracing
from repro.core.plan import (
    ContainerOpNode,
    DEFAULT_PASSES,
    ForeachNode,
    GlueNode,
    MapReduceNode,
    Plan,
    SourceInfo,
)
from repro.core.reducers import _BUILTIN, get_reducer

Array = jax.Array

__all__ = [
    "LocalHashMap",
    "LocalVector",
    "LoopInfo",
    "PlanValue",
    "Program",
    "ProgramContext",
    "ProgramStats",
    "StreamInfo",
]


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class LocalVector:
    """A shard-local vector inside a program trace (``ctx.foreach`` output).

    ``data`` is THIS shard's rows (``[per_shard, ...]``); ``n`` is the global
    true (pre-padding) length.  Usable as a ``map_reduce``/``foreach``/
    ``topk`` source within the same program — it never materialises globally.
    """

    data: Array
    n: int = dataclasses.field(metadata=dict(static=True))


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class LocalHashMap:
    """THIS shard's view of a hash target inside a program trace.

    Returned by ``ctx.map_reduce`` when the target is a ``DistHashMap``:
    ``table`` is the shard's updated ``HashTable`` (post-shuffle, post-merge).
    Usable as a source for later ops in the same program — the second pass
    reads the table in place, no collective, nothing leaves the executable.
    """

    table: C.HashTable
    reducer_name: str = dataclasses.field(metadata=dict(static=True))


@dataclasses.dataclass
class ProgramStats:
    """Per-program counters (mirrored cumulatively on ``SessionStats``)."""

    compiles: int = 0  # executables built (one per state signature)
    dispatches: int = 0  # blocks launched
    iterations: int = 0  # fused iterations run across all dispatches


@dataclasses.dataclass
class LoopInfo:
    """What one ``run_loop`` cost: the assertable fusion contract."""

    iterations: int  # iterations actually run
    dispatches: int  # executable launches (≤ ⌈iterations/unroll⌉ + exact)
    host_syncs: int  # blocking host materialisations (cond evaluations)
    converged: bool  # cond() went True before max_iters
    compiles: int  # program executables built during this loop (0 or 1)
    resumed_from: int | None = None  # checkpointed iteration restored, if any


@dataclasses.dataclass
class StreamInfo:
    """What one ``run_stream`` cost: the out-of-core streaming contract.

    ``compiles`` must be ≤ 1 regardless of block count — every block goes
    through the same executable (traced ``base`` offset, static shapes).
    """

    epochs: int  # full passes over the chunked source(s)
    n_blocks: int  # blocks per epoch
    dispatches: int  # block dispatches total (epochs x n_blocks)
    host_syncs: int  # cond evaluations (one per completed epoch)
    converged: bool  # cond() went True before max_epochs
    compiles: int  # program executables built during this stream (0 or 1)
    prefetch: bool  # double-buffered background transfer was on
    bytes_streamed: int  # host->device block bytes moved across dispatches
    resumed_from: int | None = None  # checkpointed epoch restored, if any


def _source_key(kind: str, source) -> tuple:
    """Stable identity for a source across the discovery and execution traces.

    ``DistRange`` is keyed by value (drivers re-create it freely); array-backed
    containers are keyed by the identity of their backing buffers, so
    re-wrapping the same data in a fresh dataclass still resolves.
    """
    if kind == "range":
        return ("range", source.start, source.stop, source.step)
    if kind == "vector":
        return ("vector", id(source.data), source.n)
    if kind == "chunked":
        # Host container identity: blocks are streamed in per dispatch, so
        # no backing device buffer exists to key on.
        return ("chunked", id(source), source.n)
    return ("hashmap", id(source.table.keys), id(source.table.vals))


class PlanValue:
    """A lazy dense MapReduce result inside a program trace.

    ``ctx.map_reduce`` returns one for batchable dense ops: the per-shard
    partial is computed eagerly, but the *collective* is deferred until the
    step function consumes the value — at which point every pending partial
    with the same (reducer, wire, dtype) ships in ONE concatenated
    collective (the plan's ``batch-collectives`` pass).  Consumption happens
    through the ``__jax_array__`` protocol (any jnp binary op / ``asarray``)
    or the arithmetic dunders below; ``[...]`` indexing is itself lazy, so
    ``ctx.map_reduce(...)[0]`` does not force an early flush.  A value that
    is never consumed marks its op dead (``prune-dead-sources``).
    """

    __slots__ = ("_ctx", "_idx", "_post")

    def __init__(self, ctx, idx: int, post: tuple = ()):
        self._ctx = ctx
        self._idx = idx
        self._post = post

    def _force(self) -> Array:
        base = self._ctx._materialise(self._idx)
        for f in self._post:
            base = f(base)
        return base

    # -- the JAX conversion protocol (jnp.asarray / binary ops) --------------
    def __jax_array__(self) -> Array:
        return self._force()

    def __getitem__(self, item) -> "PlanValue":
        return PlanValue(
            self._ctx, self._idx, self._post + ((lambda a, it=item: a[it]),)
        )

    def astype(self, dtype) -> Array:
        return self._force().astype(dtype)

    def reshape(self, *shape) -> Array:
        return self._force().reshape(*shape)

    # -- arithmetic: force, then defer to jnp --------------------------------
    def _bin(self, other, op, reverse=False):
        a = self._force()
        b = other._force() if isinstance(other, PlanValue) else other
        return op(b, a) if reverse else op(a, b)

    def __add__(self, o):
        return self._bin(o, jnp.add)

    def __radd__(self, o):
        return self._bin(o, jnp.add, reverse=True)

    def __sub__(self, o):
        return self._bin(o, jnp.subtract)

    def __rsub__(self, o):
        return self._bin(o, jnp.subtract, reverse=True)

    def __mul__(self, o):
        return self._bin(o, jnp.multiply)

    def __rmul__(self, o):
        return self._bin(o, jnp.multiply, reverse=True)

    def __truediv__(self, o):
        return self._bin(o, jnp.divide)

    def __rtruediv__(self, o):
        return self._bin(o, jnp.divide, reverse=True)

    def __pow__(self, o):
        return self._bin(o, jnp.power)

    def __neg__(self):
        return -self._force()

    def __lt__(self, o):
        return self._bin(o, jnp.less)

    def __le__(self, o):
        return self._bin(o, jnp.less_equal)

    def __gt__(self, o):
        return self._bin(o, jnp.greater)

    def __ge__(self, o):
        return self._bin(o, jnp.greater_equal)

    # == / != must be elementwise like every other comparison — the default
    # identity semantics would silently return False for `result == 0`.
    def __eq__(self, o):
        return self._bin(o, jnp.equal)

    def __ne__(self, o):
        return self._bin(o, jnp.not_equal)

    __hash__ = object.__hash__  # identity hash stays valid (no value hash)


# jnp functions are jit-wrapped: their argument flattening runs before any
# __jax_array__ conversion could.  Registering PlanValue as a pytree node
# whose flatten *forces* the value makes every jit boundary (jnp.maximum,
# jnp.sum, user helpers, ...) materialise it transparently — so a lazy plan
# value is a drop-in stand-in for the array inside step functions.
jax.tree_util.register_pytree_node(
    PlanValue,
    lambda pv: ((pv._force(),), None),
    lambda _aux, children: children[0],
)


def _is_plan_value(x) -> bool:
    return isinstance(x, PlanValue)


class _CountingCollectives:
    """Wraps a collectives object and counts collective *launches* — the
    quantity ``Plan.collectives_per_iter`` reports.  Used on the discovery
    trace, so the count reflects the optimized plan (batched flushes count
    once per group)."""

    def __init__(self, inner):
        self._inner = inner
        self.count = 0

    def axis_index(self):
        return self._inner.axis_index()

    def all_gather_tiled(self, x):
        self.count += 1
        return self._inner.all_gather_tiled(x)

    def all_to_all_tiled(self, x):
        self.count += 1
        return self._inner.all_to_all_tiled(x)

    def reduce(self, partial, red, wire, hier=False):
        self.count += 1
        return self._inner.reduce(partial, red, wire, hier=hier)

    def reduce_feedback(self, partial, red, wire, residual, hier=False):
        self.count += 1
        return self._inner.reduce_feedback(
            partial, red, wire, residual, hier=hier
        )


class ProgramContext:
    """What ``step_fn`` sees: session-API lookalikes that compose in-trace.

    ``ctx.map_reduce`` / ``ctx.foreach`` / ``ctx.topk`` mirror the
    ``BlazeSession`` methods but run *inside* the fused program's shard body
    — no jit, no dispatch, no per-op stats; each op's collective is inlined
    (and possibly batched with its neighbours').  The same user code
    therefore reads identically in per-op and program form.

    Two modes share this class: ``"discover"`` *builds* the logical plan
    (nodes, sources, batch groups, CSE aliases, dead ops) while tracing under
    ``jax.eval_shape``; ``"execute"`` *consumes* a finished plan inside the
    fused ``shard_map`` body — skipping pruned nodes, reusing CSE'd results,
    and flushing the same batched collectives.
    """

    def __init__(
        self, n_shards: int, mode: str, coll=None, operands=None,
        residuals=None, hash_tables=None, plan: Plan | None = None,
        passes: tuple = DEFAULT_PASSES, tuning=None, overrides=None,
        degraded=None, n_nodes: int = 1, hierarchical: bool = True,
    ):
        self._n_shards = n_shards
        self._n_nodes = n_nodes
        self._hierarchical = hierarchical
        self._mode = mode  # "discover" | "execute"
        # discover-mode autotuning hooks: ``tuning`` is the session's
        # TuningCache (cached winners apply to every node built), and
        # ``overrides`` maps tune_key -> candidate TunedConfig for the
        # throwaway measurement variants Program._maybe_tune builds.
        # ``degraded`` is the session's set of kernel-faulted tune_keys:
        # nodes matching it resolve straight to eager on (re)discovery.
        self._tuning = tuning
        self._overrides = overrides or {}
        self._degraded = degraded
        self._tune_info: dict[int, tuple] = {}  # idx -> candidate-grid params
        inner = (
            coll if coll is not None
            else _mr.AbstractCollectives(n_shards, n_nodes=n_nodes)
        )
        if mode == "discover":
            inner = _CountingCollectives(inner)
        self._coll = inner
        self._operands = operands or {}  # source key -> local operand tuple
        self._plan = plan  # execute mode: the optimized plan to replay
        self._passes = tuple(passes)
        self._batch = "batch-collectives" in self._passes
        self._cse = "cse" in self._passes
        self._prune = "prune-dead-sources" in self._passes
        # -- discover-mode plan-building state --------------------------------
        self._nodes: list = []  # call-order plan nodes
        self._sources: dict[tuple, Any] = {}  # key -> source, ordered
        self._local_producers: dict[int, int] = {}  # id(array) -> node idx
        self._cse_index: dict[tuple, int] = {}  # cse key -> node idx
        self._groups: dict[int, list[int]] = {}
        self._group_keys: dict[int, tuple] = {}
        self._hash_targets: dict[tuple, Any] = {}
        # -- shared runtime state ---------------------------------------------
        self._call_i = 0  # ctx-op call counter (node index)
        self._pending: list[int] = []  # deferred ops awaiting their collective
        self._partials: dict[int, tuple] = {}  # idx -> (partial, red, wire, hier)
        self._totals: dict[int, Array] = {}  # idx -> reduced (pre-merge) total
        self._results: dict[int, Array] = {}  # idx -> target-merged result
        self._meta: dict[int, tuple] = {}  # idx -> (red, target) for the merge
        self._residuals = residuals if residuals is not None else []
        self._res_i = 0
        # hash-target state: key -> this shard's HashTable (current value)
        self._hash_tables: dict[tuple, C.HashTable] = (
            hash_tables if hash_tables is not None else {}
        )

    # -- source resolution ----------------------------------------------------

    def _local_for(self, kind: str, source):
        if self._mode == "discover":
            self._sources.setdefault(_source_key(kind, source), source)
            if kind == "range":
                return None
            if kind == "vector":
                per = source.data.shape[0] // self._n_shards
                return (
                    jnp.zeros((per,) + source.data.shape[1:], source.data.dtype),
                    source.n,
                )
            if kind == "chunked":
                # Shape-faithful stand-in for ONE resident block: the
                # executable only ever sees a block's worth of rows plus the
                # traced base offset.
                per = source.block_rows // self._n_shards
                return (
                    jnp.zeros((per,) + source.shape_tail, source.dtype),
                    source.n,
                    jnp.zeros((), jnp.int32),
                )
            keys, vals = source.table.keys, source.table.vals
            return (
                jnp.full(keys.shape[1:], C.EMPTY_KEY, keys.dtype),
                jnp.zeros(vals.shape[1:], vals.dtype),
            )
        if kind == "range":
            return None
        return _mr._local_view(
            kind, source, self._operands[_source_key(kind, source)]
        )

    def _resolve_program_source(self, source):
        """(kind, static source, local view, src desc, source key) for any
        in-program source — the session containers plus the program-local
        ``LocalVector`` / ``LocalHashMap`` intermediates."""
        if isinstance(source, LocalVector):
            prod = self._local_producers.get(id(source.data), "?")
            return "vector", None, (source.data, source.n), f"local[{prod}]", None
        if isinstance(source, LocalHashMap):
            prod = self._local_producers.get(id(source.table.keys), "?")
            return (
                "hashmap", None,
                (source.table.keys, source.table.vals), f"local[{prod}]", None,
            )
        kind = _mr._source_kind(source)
        key = _source_key(kind, source)
        desc = plan_mod.source_desc(kind, source)
        return kind, source, self._local_for(kind, source), desc, key

    def _resolve_vector_source(self, v, what: str):
        """(data, n, src desc, source key) for the vector-only ctx ops
        (``foreach``, ``topk``): a ``DistVector`` or a ``LocalVector``."""
        if isinstance(v, LocalVector):
            prod = self._local_producers.get(id(v.data), "?")
            return v.data, v.n, f"local[{prod}]", None
        if isinstance(v, C.DistVector):
            data, n = self._local_for("vector", v)
            return (
                data, n, plan_mod.source_desc("vector", v),
                _source_key("vector", v),
            )
        raise TypeError(
            f"{what} needs a DistVector or LocalVector, got {type(v)}"
        )

    # -- plan-node bookkeeping -------------------------------------------------

    def _next_node(self, expect_type=None):
        """Execute mode: the plan node matching this ctx call."""
        idx = self._call_i
        self._call_i += 1
        if self._plan is None:
            return idx, None
        node = self._plan.nodes[idx]
        if expect_type is not None and not isinstance(node, expect_type):
            raise RuntimeError(
                f"program trace diverged from its plan at node {idx}: "
                f"expected {expect_type.__name__}, found {type(node).__name__}"
            )
        return idx, node

    def _cse_key(self, kind, source_key, local, mapper, red, target, engine,
                 wire, key_range, env):
        """Identity of a node's *reduced total* — the part CSE can share.

        The target merge is applied per node at materialisation (totals, not
        merged results, are cached), so two ops differing only in their
        target arrays still dedupe.  Dynamic inputs are compared by tracer
        identity: the same state leaf or ``foreach`` output reused across ops
        keys equal; anything recomputed keys distinct (conservative).
        """
        if source_key is not None:
            src_ident = source_key
        elif isinstance(local, tuple):  # local view (data, n) / (keys, vals)
            src_ident = ("local",) + tuple(id(x) for x in local)
        else:
            src_ident = ("local", id(local))
        env_ids = tuple(id(x) for x in jax.tree_util.tree_leaves(env))
        target = jnp.asarray(target)
        return (
            kind, src_ident, mapper, id(red), engine, wire, key_range,
            tuple(target.shape), str(target.dtype), env_ids,
        )

    # -- deferred collectives (the batch-collectives pass) ---------------------

    def _total_of(self, idx: int) -> Array:
        """The op's reduced total (pre target-merge) — the sharable part."""
        if idx in self._totals:
            return self._totals[idx]
        node = (
            self._plan.nodes[idx] if self._plan is not None else
            (self._nodes[idx] if idx < len(self._nodes) else None)
        )
        if isinstance(node, MapReduceNode) and node.cse_of is not None:
            return self._total_of(node.cse_of)
        if idx in self._pending:
            # Mid-step consumption: flush EVERYTHING pending — independent
            # reductions that happen to be in flight batch into one
            # collective per (reducer, wire, dtype).
            self._flush()
            return self._totals[idx]
        raise RuntimeError(f"plan node {idx} has no result to materialise")

    def _materialise(self, idx: int) -> Array:
        if idx in self._results:
            return self._results[idx]
        node = (
            self._plan.nodes[idx] if self._plan is not None else
            (self._nodes[idx] if idx < len(self._nodes) else None)
        )
        if (
            isinstance(node, MapReduceNode) and node.dead
            and self._mode == "execute"
        ):
            raise RuntimeError(
                f"plan node {idx} was pruned as dead but its result was "
                "consumed — the execution trace diverged from discovery"
            )
        red, target = self._meta[idx]
        total = self._total_of(idx)
        out = red.combine(target, total.astype(target.dtype))
        self._results[idx] = out
        return out

    def _flush(self, needed: set | None = None):
        idxs = [i for i in self._pending if needed is None or i in needed]
        if not idxs:
            return
        self._pending = [i for i in self._pending if i not in set(idxs)]
        by_key: dict[tuple, list[int]] = {}
        for i in idxs:
            partial, red, wire, hier = self._partials[i]
            by_key.setdefault(
                (red.name, wire, str(partial.dtype), hier), []
            ).append(i)
        for key, members in by_key.items():
            if len(members) == 1 or not self._batch:
                for i in members:
                    partial, red, wire, hier = self._partials[i]
                    self._totals[i] = self._coll.reduce(
                        partial, red, wire, hier=hier
                    )
                continue
            # One fused collective for the whole group: flatten, concatenate,
            # reduce once, split.  Exact for every built-in reducer — psum /
            # pmin / pmax and the gathered prod fold are all elementwise, so
            # reducing the concatenation is bit-identical to reducing each
            # buffer alone.
            _p0, red, wire, hier = self._partials[members[0]]
            flats = [self._partials[i][0].reshape(-1) for i in members]
            sizes = [f.shape[0] for f in flats]
            total_cat = self._coll.reduce(
                jnp.concatenate(flats), red, wire, hier=hier
            )
            off = 0
            for i, sz in zip(members, sizes):
                partial, _r, _w, _h = self._partials[i]
                self._totals[i] = total_cat[off:off + sz].reshape(partial.shape)
                off += sz
            if self._mode == "discover":
                gid = len(self._groups)
                self._groups[gid] = list(members)
                self._group_keys[gid] = key
                for i in members:
                    self._nodes[i].group = gid

    def _finalize_state(self, out):
        """Materialise every plan value the step returns; whatever is still
        pending afterwards was never consumed — the op is dead."""
        needed: set[int] = set()

        def _collect(x):
            if isinstance(x, PlanValue):
                tgt = x._idx
                node = (
                    self._plan.nodes[tgt] if self._plan is not None
                    else self._nodes[tgt]
                )
                if isinstance(node, MapReduceNode) and node.cse_of is not None:
                    needed.add(node.cse_of)
                needed.add(tgt)
            return x

        jax.tree_util.tree_map(_collect, out, is_leaf=_is_plan_value)
        # With pruning on, flush only what the state needs (the rest is
        # dead); with it off, every op's collective still runs.
        self._flush(needed=needed if self._prune else None)
        out = jax.tree_util.tree_map(
            lambda x: x._force() if isinstance(x, PlanValue) else x,
            out, is_leaf=_is_plan_value,
        )
        if self._mode == "discover":
            for i in self._pending:
                self._nodes[i].dead = True
        self._pending = []
        return out

    # -- the in-program API ---------------------------------------------------

    @property
    def shard_index(self) -> Array:
        """This shard's mesh coordinate (0 under discovery)."""
        return self._coll.axis_index()

    def map_reduce(
        self, source, mapper: Callable, reducer, target, *,
        engine: str = "eager", wire: str = "none", env: Any = None,
        shuffle_slack: float = 2.0, key_range: int | None = None,
    ):
        """One MapReduce op, fused into the surrounding program.

        Same contract as ``BlazeSession.map_reduce``, except the result is a
        traced value inside the program and no per-op stats exist — the
        whole program is one dispatch.  Dense targets return the merged
        result (merge into ``target`` included) as a lazy :class:`PlanValue`
        whose collective is deferred and batched with its neighbours'
        (plain jnp use materialises it transparently).  ``DistHashMap``
        targets return a ``LocalHashMap`` — this shard's updated table,
        readable as a source by later ops in the same iteration; the table
        itself is per-shard state threaded through the fused loop and across
        dispatches (``Program.hash_result`` materialises it).
        ``wire="int8"`` sums additionally get error feedback: the per-shard
        quantization residual is carried through the device-resident loop
        *and* across dispatches (the executable returns it and the next
        block feeds it back in), so iterative reductions stay unbiased for
        the lifetime of the program (``RealCollectives.reduce_feedback``).
        """
        red = get_reducer(reducer)
        env = jax.tree_util.tree_map(
            lambda x: x._force() if isinstance(x, PlanValue) else x,
            env, is_leaf=_is_plan_value,
        )
        if isinstance(target, C.DistHashMap):
            return self._map_reduce_hash(
                source, mapper, red, target, engine=engine, env=env,
                shuffle_slack=shuffle_slack, key_range=key_range,
            )
        target = jnp.asarray(target)
        if self._mode == "execute" and self._plan is not None:
            # Pruned/CSE'd nodes are skipped BEFORE source resolution — a
            # source only they read is never shipped into the executable.
            peek = self._plan.nodes[self._call_i]
            if isinstance(peek, MapReduceNode) and (
                peek.dead or peek.cse_of is not None
            ):
                idx, _ = self._next_node(MapReduceNode)
                self._meta[idx] = (red, target)
                return PlanValue(self, idx)
        kind, src_static, local, src_desc, source_key = (
            self._resolve_program_source(source)
        )

        if self._mode == "discover":
            node = plan_mod.build_mapreduce_node(
                idx=self._call_i, kind=kind, src=src_desc,
                source_key=source_key, mapper=mapper, red=red, target=target,
                engine=engine, wire=wire, key_range=key_range, env=env,
                tuning=self._tuning, degraded=self._degraded,
                n_nodes=self._n_nodes, hierarchical=self._hierarchical,
            )
            ov = self._overrides.get(node.tune_key)
            if ov is not None:
                plan_mod.apply_tuned(node, red, ov)
            self._call_i += 1
            self._nodes.append(node)
            self._meta[node.idx] = (red, target)
            v = math.prod(target.shape[1:]) if target.ndim > 1 else 1
            self._tune_info[node.idx] = (
                "dense", target.shape[0] if target.ndim else 0, v, red.name,
                str(target.dtype), None, red.pallas_segment is not None,
            )
            if self._cse and not (
                wire == "int8" and red.name == "sum"
            ):
                ck = self._cse_key(
                    kind, source_key, local, mapper, red, target,
                    node.engine, wire, key_range, env,
                )
                hit = self._cse_index.get(ck)
                if hit is not None:
                    node.cse_of = hit
                    return PlanValue(self, node.idx)
                self._cse_index[ck] = node.idx
        else:
            idx, node = self._next_node(MapReduceNode)
            self._meta[idx] = (red, target)
            if node is None:
                node = plan_mod.build_mapreduce_node(
                    idx=idx, kind=kind, src=src_desc, source_key=source_key,
                    mapper=mapper, red=red, target=target, engine=engine,
                    wire=wire, key_range=key_range, env=env,
                    n_nodes=self._n_nodes, hierarchical=self._hierarchical,
                )
            elif node.cse_of is not None:
                return PlanValue(self, node.idx)
            elif node.dead:
                return PlanValue(self, node.idx)

        resolved = node.engine
        feedback = (
            wire == "int8" and red.name == "sum"
            and resolved in ("eager", "pallas")
        )
        node.feedback = feedback
        # Deferrable (and therefore batchable/prunable): a built-in
        # reducer's eager or pallas plan without error feedback — exactly
        # the ops whose collective is one elementwise reduce of a partial.
        deferrable = (
            resolved in ("eager", "pallas")
            and not feedback
            and red is _BUILTIN.get(red.name)
            and (self._batch or self._prune)
        )
        stage, _ = _mr.dense_shard_stage(
            kind, src_static, mapper, red, target, resolved, wire,
            self._n_shards, with_stats=False, feedback=feedback,
            collect=not deferrable, tuned=getattr(node, "tuned", None),
            hier=node.hier,
        )
        residual = None
        if feedback:
            if self._mode == "discover":
                node.residual_spec = (tuple(target.shape), jnp.float32)
                residual = jnp.zeros(target.shape, jnp.float32)
            else:
                residual = self._residuals[self._res_i]
        total, _live, _kp, new_residual = stage(env, local, self._coll, residual)
        if feedback:
            if self._mode == "execute":
                self._residuals[self._res_i] = new_residual
            self._res_i += 1
        if deferrable:
            self._partials[node.idx] = (total, red, wire, node.hier)
            self._pending.append(node.idx)
            return PlanValue(self, node.idx)
        self._totals[node.idx] = total
        self._results[node.idx] = red.combine(target, total.astype(target.dtype))
        return self._results[node.idx]

    def _map_reduce_hash(
        self, source, mapper, red, target, *, engine, env, shuffle_slack,
        key_range,
    ):
        """Hash-target op inside a program: per-shard table state.

        The target is identified by its backing buffers (stable across
        iterations — drivers capture the same ``DistHashMap``); its table is
        fetched from / written back to the threaded hash state, so several
        ops (or iterations) targeting the same map compose sequentially.
        Never deferred, CSE'd or pruned: the op *mutates* threaded state.
        """
        kind, src_static, local, src_desc, source_key = (
            self._resolve_program_source(source)
        )
        if self._mode == "discover":
            node = plan_mod.build_mapreduce_node(
                idx=self._call_i, kind=kind, src=src_desc,
                source_key=source_key, mapper=mapper, red=red, target=target,
                engine=engine, wire="none", key_range=key_range, env=env,
                tuning=self._tuning, degraded=self._degraded,
                n_nodes=self._n_nodes, hierarchical=self._hierarchical,
            )
            ov = self._overrides.get(node.tune_key)
            if ov is not None:
                plan_mod.apply_tuned(node, red, ov)
            self._call_i += 1
            self._nodes.append(node)
            vals = target.table.vals
            v = math.prod(vals.shape[2:]) if vals.ndim > 2 else 1
            self._tune_info[node.idx] = (
                "hash", 0, v, red.name, str(vals.dtype), key_range,
                red.pallas_hash is not None,
            )
        else:
            _, node = self._next_node(MapReduceNode)
        resolved = node.engine if node is not None else plan_mod.resolve_engine(
            engine, target, red
        )
        tkey = ("hashtarget",) + _source_key("hashmap", target)[1:]
        if tkey not in self._hash_tables:
            if self._mode != "discover":
                raise ValueError(
                    "hash target not registered during discovery — targets "
                    "must be the same DistHashMap objects across iterations"
                )
            # Shape-faithful per-shard stand-in (strip the [n_shards] dim).
            keys, vals = target.table.keys, target.table.vals
            self._hash_tables[tkey] = C.HashTable(
                jnp.full(keys.shape[1:], C.EMPTY_KEY, keys.dtype),
                jnp.full(
                    vals.shape[1:], red.identity(vals.dtype), vals.dtype
                ),
                jnp.zeros((), jnp.int32),
            )
        if self._mode == "discover":
            self._hash_targets.setdefault(tkey, target)
        table = self._hash_tables[tkey]
        stage, _meta = _mr.hash_shard_stage(
            kind, src_static, mapper, red, target.table.vals.dtype, resolved,
            shuffle_slack, self._n_shards, key_range=key_range,
            tuned=getattr(node, "tuned", None),
        )
        table = stage(env, table, local, self._coll)[0]
        self._hash_tables[tkey] = table
        if self._mode == "discover" and node is not None:
            self._local_producers[id(table.keys)] = node.idx
        return LocalHashMap(table, red.name)

    def foreach(self, v, fn: Callable, env: Any = None) -> LocalVector:
        """Elementwise map over a ``DistVector`` source or a ``LocalVector``.

        Returns a ``LocalVector`` — the result stays on-shard, feeding later
        ops in the same program without any collective.
        """
        env = jax.tree_util.tree_map(
            lambda x: x._force() if isinstance(x, PlanValue) else x,
            env, is_leaf=_is_plan_value,
        )
        data, n, src_desc, source_key = self._resolve_vector_source(
            v, "ctx.foreach"
        )
        if self._mode == "discover":
            node = ForeachNode(
                idx=self._call_i, src=src_desc, source_key=source_key, fn=fn
            )
            self._call_i += 1
            self._nodes.append(node)
            idx = node.idx
        else:
            idx, _ = self._next_node(ForeachNode)
        out = jax.vmap(fn)(data) if env is None else jax.vmap(
            lambda x: fn(x, env)
        )(data)
        if self._mode == "discover":
            self._local_producers[id(out)] = idx
        return LocalVector(out, n)

    def topk(
        self, v, k: int, score_fn: Callable | None = None, env: Any = None,
        engine: str | None = None,
    ) -> tuple[Array, Array]:
        """Container-level top-k inside a program: per-shard ``lax.top_k``,
        one all_gather of ``k·n_shards`` candidates, global re-select.

        Returns replicated ``(rows [m, ...], scores [m])`` with
        ``m = min(k, kk·n_shards)``.  The plan records this as a
        :class:`ContainerOpNode`; an ``engine=`` request is *surfaced* on the
        node (and in ``explain``) rather than silently dropped — a container
        op's plan is fixed by the container, no engine can change it.
        """
        env = jax.tree_util.tree_map(
            lambda x: x._force() if isinstance(x, PlanValue) else x,
            env, is_leaf=_is_plan_value,
        )
        data, n, src_desc, source_key = self._resolve_vector_source(
            v, "ctx.topk"
        )
        if self._mode == "discover":
            score_name = (
                "value" if score_fn is None
                else getattr(score_fn, "__qualname__", repr(score_fn))
            )
            self._nodes.append(ContainerOpNode(
                idx=self._call_i, op="topk", src=src_desc,
                source_key=source_key, params=f"k={k} score={score_name}",
                engine_requested=engine,
            ))
            self._call_i += 1
        else:
            self._next_node(ContainerOpNode)
        per = data.shape[0]
        kk = min(k, per)
        base = self._coll.axis_index() * per
        if score_fn is None:
            scores = data.astype(jnp.float32)
        elif env is None:
            scores = jax.vmap(score_fn)(data)
        else:
            scores = jax.vmap(lambda x: score_fn(x, env))(data)
        idx_in = jnp.arange(per) + base
        scores = jnp.where(idx_in < n, scores, -jnp.inf)
        s, i = jax.lax.top_k(scores, kk)
        cand = jnp.take(data, i, axis=0)
        gs = self._coll.all_gather_tiled(s)
        gc = self._coll.all_gather_tiled(cand)
        m = min(k, gs.shape[0])
        s2, i2 = jax.lax.top_k(gs, m)
        return jnp.take(gc, i2, axis=0), s2

    # -- plan assembly (discover mode) ----------------------------------------

    def build_plan(self, state_desc: str, passes: tuple) -> Plan:
        nodes = list(self._nodes)
        nodes.append(GlueNode(idx=len(nodes), desc="state update (user glue)"))
        # prune-dead-sources: a source is live iff some live node reads it.
        live_keys: set[tuple] = set()
        for n in nodes:
            if isinstance(n, MapReduceNode) and (n.dead or n.cse_of is not None):
                continue
            sk = getattr(n, "source_key", None)
            if sk is not None:
                live_keys.add(sk)
        sources = [
            SourceInfo(
                key=k,
                desc=plan_mod.source_desc(_mr._source_kind(s), s),
                source=s,
                pruned=self._prune and k not in live_keys,
            )
            for k, s in self._sources.items()
        ]
        dead = sum(
            1 for n in nodes
            if isinstance(n, MapReduceNode) and n.dead
        )
        cse_hits = sum(
            1 for n in nodes
            if isinstance(n, MapReduceNode) and n.cse_of is not None
        )
        n_coll = self._coll.count  # _CountingCollectives in discover mode
        unbatched = n_coll + sum(
            len(g) - 1 for g in self._groups.values()
        )
        residual_specs = [
            n.residual_spec
            for n in nodes
            if isinstance(n, MapReduceNode) and n.residual_spec is not None
        ]
        return Plan(
            nodes=nodes,
            sources=sources,
            state_desc=state_desc,
            n_shards=self._n_shards,
            passes=passes,
            groups=dict(self._groups),
            group_keys=dict(self._group_keys),
            n_nodes=self._n_nodes,
            collectives_per_iter=n_coll,
            collectives_unbatched=unbatched,
            cse_hits=cse_hits,
            dead_ops=dead,
            pruned_sources=sum(1 for s in sources if s.pruned),
            residual_specs=residual_specs,
            hash_targets=dict(self._hash_targets),
            tune_info=dict(self._tune_info),
        )


def _as_checkpoint_manager(checkpoint):
    """Accept a ``CheckpointManager``, a directory path, or ``None``."""
    if checkpoint is None:
        return None
    if isinstance(checkpoint, str):
        from repro.checkpoint.manager import CheckpointManager

        return CheckpointManager(checkpoint)
    return checkpoint


def _state_desc(state) -> str:
    leaves, treedef = jax.tree_util.tree_flatten(state)
    descs = ",".join(
        f"{str(jnp.asarray(x).dtype)}[{'x'.join(map(str, jnp.shape(x)))}]"
        for x in leaves
    )
    return f"{treedef.num_leaves} leaves: {descs}"


class Program:
    """A user step function planned, optimized and lowered to one executable
    per state signature.

    Built by ``BlazeSession.program(step_fn)``; ``step_fn(ctx, state)`` must
    return a state pytree with the same structure/shapes/dtypes (it is a
    ``fori_loop`` carry).  Call ``program(state, n_iters)`` for one dispatch
    of ``n_iters`` fused iterations, or drive it with
    ``session.run_loop(...)``.  The trip count is traced, so full blocks and
    the remainder block share the single compiled executable.

    ``program.plan`` (after :meth:`build` or the first dispatch) is the
    optimized :class:`repro.core.plan.Plan`; ``session.explain(program)``
    renders it.  ``passes=()`` disables the optimizer (CSE, collective
    batching, dead-source pruning) for apples-to-apples comparisons —
    ``benchmarks/paper_benchmarks.py::bench5_plan_batching`` uses exactly
    that to report collectives-per-iteration before/after.
    """

    def __init__(
        self, session, step_fn: Callable, *, mesh: Mesh | None = None,
        passes: tuple | None = None, tune: bool = False,
        overrides: dict | None = None, hierarchical: bool = True,
    ):
        self._session = session
        self._step_fn = step_fn
        self._mesh = mesh if mesh is not None else session.mesh
        self._n_shards = C.shard_count(self._mesh)
        # ``hierarchical=False`` keeps collectives flat even on a multi-node
        # mesh — the A/B baseline the scaling bench compares against.
        self._hierarchical = bool(hierarchical)
        self._n_nodes = C.n_nodes(self._mesh) if self._hierarchical else 1
        self._passes = DEFAULT_PASSES if passes is None else tuple(passes)
        # ``tune``: on first build per state signature, measure the candidate
        # grid for every tunable op (see _maybe_tune) and cache winners in
        # the session's TuningCache.  ``overrides`` pins tune_key -> config
        # for the throwaway measurement variants — such a variant never
        # recursively tunes.
        self._tune = bool(tune)
        self._overrides = overrides
        self._cache: dict = {}  # state signature -> (jitted fused fn, operands)
        self._plans: dict = {}  # state signature -> optimized Plan
        # state signature -> live per-shard error-feedback residuals, carried
        # ACROSS dispatches for the lifetime of this Program
        self._residual_state: dict = {}
        # state signature -> (hash-target key order, tuple of per-target
        # (keys, vals, overflow) sharded arrays) — like residuals, hash
        # tables are per-shard state that outlives each dispatch
        self._hash_state: dict = {}
        # state signature -> (stream-source key order, chunked containers):
        # out-of-core sources whose (data, base) operands arrive per
        # dispatch (run_stream) instead of being baked into the cache entry
        self._stream_state: dict = {}
        # signatures whose current executable has run (so has compiled)
        self._ran: set = set()
        self._last_sig = None  # signature of the most recent dispatch
        self._last_args = None  # its arguments, for compiled_text()
        self.plan: Plan | None = None  # most recently built plan
        self.stats = ProgramStats()
        self.feedback_slots = 0  # error-feedback residual slots (int8 sums)
        self.hash_slots = 0  # hash-target table slots threaded per iteration

    # -- build ---------------------------------------------------------------

    def _discover(self, state) -> Plan:
        ctx = ProgramContext(
            self._n_shards, "discover", passes=self._passes,
            tuning=self._session.tuning, overrides=self._overrides,
            degraded=getattr(self._session, "_degraded", None),
            n_nodes=self._n_nodes, hierarchical=self._hierarchical,
        )

        def run(s):
            out = self._step_fn(ctx, s)
            return ctx._finalize_state(out)

        out = jax.eval_shape(run, state)
        in_flat, in_tree = jax.tree_util.tree_flatten(state)
        out_flat, out_tree = jax.tree_util.tree_flatten(out)
        if in_tree != out_tree:
            raise ValueError(
                "step_fn must return a state pytree with the same structure "
                f"it was given (got {out_tree}, want {in_tree})"
            )
        for i, (a, b) in enumerate(zip(in_flat, out_flat)):
            a_shape, a_dt = jnp.shape(a), jnp.asarray(a).dtype
            if (a_shape, a_dt) != (b.shape, b.dtype):
                raise ValueError(
                    "step_fn must preserve state leaf shapes/dtypes (it is a "
                    f"fori_loop carry); leaf {i} went from {a_shape}/{a_dt} "
                    f"to {b.shape}/{b.dtype}"
                )
        return ctx.build_plan(_state_desc(state), self._passes)

    def _maybe_tune(self, state) -> None:
        """First-dispatch autotuning: measure the candidate grid and cache
        the winners in the session's TuningCache.

        A probe discovery finds every tunable op (kernel available, not
        ``naive``, not already measured for its ``tune_key``).  Candidate
        configurations are index-aligned across ops — variant ``j`` pins
        each op to its ``min(j, len-1)``-th candidate — and each variant is
        a throwaway ``Program`` with ``overrides`` set, dispatched once to
        warm/compile and once timed end-to-end.  The fastest variant's
        per-op configs are stored keyed by ``tune_key``, so the real build
        that follows (and any later program/map_reduce/serve dispatch with
        the same op) picks them up from the cache.  Streamed (chunked-
        source) programs are skipped: their operands arrive per dispatch.
        """
        from repro.core import cost as cost_mod

        session = self._session
        tuning = session.tuning
        probe = self._discover(state)
        if any(
            _mr._source_kind(s.source) == "chunked"
            for s in probe.live_sources()
        ):
            return
        cand_lists: list[tuple[str, list]] = []
        seen: set[str] = set()
        for n in probe.mapreduce_nodes():
            if n.dead or n.cse_of is not None:
                continue
            if n.tuned is not None or n.tune_key in seen:
                continue
            if tuning.peek(n.tune_key) is not None:
                continue
            info = probe.tune_info.get(n.idx)
            if info is None:
                continue
            tkind, k, v, red_name, dtype_s, key_range, has_kernel = info
            if not has_kernel or n.engine_requested == "naive":
                continue
            dtype = jnp.dtype(dtype_s)
            if tkind == "hash":
                cands = cost_mod.hash_tuning_candidates(
                    v, red_name, dtype, key_range=key_range
                )
            else:
                cands = cost_mod.dense_tuning_candidates(k, v, red_name, dtype)
            if len(cands) < 2:
                continue
            seen.add(n.tune_key)
            cand_lists.append((n.tune_key, cands))
        if not cand_lists:
            return
        n_variants = max(len(c) for _, c in cand_lists)
        best_wall, best_set = None, None
        measured = 0
        for j in range(n_variants):
            ov = {
                tk: cands[min(j, len(cands) - 1)] for tk, cands in cand_lists
            }
            variant = Program(
                session, self._step_fn, mesh=self._mesh, passes=self._passes,
                overrides=ov, hierarchical=self._hierarchical,
            )
            try:
                faults.fault_point("tuning.measure")
                out = variant(state, 1)
                jax.block_until_ready(jax.tree_util.tree_leaves(out))
                t0 = time.perf_counter()
                out = variant(state, 1)
                jax.block_until_ready(jax.tree_util.tree_leaves(out))
                wall = time.perf_counter() - t0
            except faults.InjectedFault as e:
                # A faulted candidate is simply not measured — tuning is an
                # optimisation, so the fault is absorbed, never retried.  A
                # real exception propagates: it is a fault, not a slow config.
                faults.record("absorbed", e)
                continue
            measured += 1
            if best_wall is None or wall < best_wall:
                best_wall, best_set = wall, ov
        tuning.record_measurements(measured)
        session.stats.tune_measurements += measured
        if best_set is None:
            return
        for tk, cfg in best_set.items():
            tuning.put(
                tk,
                dataclasses.replace(cfg, source="measured", wall_s=best_wall),
            )

    def build(self, state) -> Plan:
        """Discover, optimize and lower the plan for ``state``'s signature
        WITHOUT dispatching (compilation itself stays lazy under jit).
        Returns the optimized :class:`Plan` — what ``session.explain``
        renders."""
        key = _mr._abstract(state)
        self._build(state)
        return self._plans[key]

    def _build(self, state):
        key = _mr._abstract(state)
        if key in self._cache:
            self.plan = self._plans[key]
            return self._cache[key]
        if self._tune and self._overrides is None:
            self._maybe_tune(state)
        plan = self._discover(state)
        self._plans[key] = plan
        self.plan = plan
        self.feedback_slots = len(plan.residual_specs)
        self.hash_slots = len(plan.hash_targets)
        n_shards = self._n_shards
        n_nodes = self._n_nodes
        hierarchical = self._hierarchical
        mesh = self._mesh
        step_fn = self._step_fn
        passes = self._passes

        operands: list = []
        specs: list = []
        source_keys: list[tuple] = []
        sizes: list[int] = []
        stream_keys: list[tuple] = []
        stream_sources: list = []
        for s in plan.live_sources():
            kind = _mr._source_kind(s.source)
            if kind == "chunked":
                # Out-of-core source: its (data, base) operands are supplied
                # fresh per dispatch by run_stream — never baked into the
                # cache entry like device-resident containers below.
                stream_keys.append(s.key)
                stream_sources.append(s.source)
                continue
            ops, sp = _mr._source_operands(kind, s.source, mesh)
            operands.extend(ops)
            specs.extend(sp)
            source_keys.append(s.key)
            sizes.append(len(ops))
        n_res = len(plan.residual_specs)
        hash_keys = list(plan.hash_targets)
        n_hash = len(hash_keys)
        n_stream = len(stream_keys)

        def shard_body(state_, n_iters, *flat):
            # flat = per-op feedback residuals, then per-target hash tables
            # (both sharded: each shard carries its own), then (data, base)
            # per streamed block source, then the live source operands.
            res_in = flat[:n_res]
            hash_in = flat[n_res:n_res + 3 * n_hash]
            stream_in = flat[n_res + 3 * n_hash:n_res + 3 * n_hash + 2 * n_stream]
            flat_ops = flat[n_res + 3 * n_hash + 2 * n_stream:]
            # Spans both mesh axes on a 2-D mesh; whether a given reduce is
            # hierarchical is per-node (``hier=`` on each call), so the flat
            # A/B baseline shares this same object.
            coll = _mr.make_collectives(mesh, n_shards)
            op_map, i = {}, 0
            for sk, k in zip(source_keys, sizes):
                op_map[sk] = tuple(flat_ops[i:i + k])
                i += k
            for j, sk in enumerate(stream_keys):
                op_map[sk] = (stream_in[2 * j], stream_in[2 * j + 1])

            def one_step(_, carry):
                st, residuals, tables = carry
                ctx = ProgramContext(
                    n_shards, "execute", coll=coll, operands=op_map,
                    residuals=list(residuals),
                    hash_tables=dict(zip(hash_keys, tables)),
                    plan=plan, passes=passes,
                    n_nodes=n_nodes, hierarchical=hierarchical,
                )
                new_st = ctx._finalize_state(step_fn(ctx, st))
                return (
                    new_st,
                    tuple(ctx._residuals),
                    tuple(ctx._hash_tables[hk] for hk in hash_keys),
                )

            res0 = tuple(r[0] for r in res_in)  # drop the local shard dim
            h0 = tuple(
                C.HashTable(
                    hash_in[3 * i_][0], hash_in[3 * i_ + 1][0],
                    hash_in[3 * i_ + 2][0],
                )
                for i_ in range(n_hash)
            )
            out_state, res_out, h_out = jax.lax.fori_loop(
                0, n_iters, one_step, (state_, res0, h0)
            )
            return (
                out_state,
                tuple(r[None] for r in res_out),
                tuple(
                    (t.keys[None], t.vals[None], t.overflow[None])
                    for t in h_out
                ),
            )

        d = C.data_pspec(self._mesh)
        stream_specs: tuple = ()
        for _ in stream_keys:
            stream_specs += (d, P())  # block rows sharded, base replicated
        fused = shard_map(
            shard_body,
            mesh=self._mesh,
            in_specs=(
                (P(), P()) + (d,) * (n_res + 3 * n_hash)
                + stream_specs + tuple(specs)
            ),
            out_specs=(P(), d, d),
            check_vma=False,
        )
        # Residual AND hash-table state outlive the dispatch: the executable
        # returns the updated per-shard arrays and the next dispatch feeds
        # them back in, so both stay live across blocks (even unroll=1).
        # A rebuild for an already-carried signature (engine degradation
        # dropped the executable mid-run) keeps the live carry — degradation
        # must not lose accumulated state.
        if key not in self._residual_state:
            self._residual_state[key] = tuple(
                jnp.zeros((n_shards,) + shape, dtype)
                for shape, dtype in plan.residual_specs
            )
        if key not in self._hash_state:
            self._hash_state[key] = (
                hash_keys,
                tuple(
                    (hm.table.keys, hm.table.vals, hm.table.overflow)
                    for hm in plan.hash_targets.values()
                ),
            )
        self._stream_state[key] = (tuple(stream_keys), tuple(stream_sources))
        entry = (jax.jit(fused), tuple(operands))
        self._cache[key] = entry
        self.stats.compiles += 1
        self._session.stats.program_compiles += 1
        return entry

    def compiled_text(self) -> str:
        """HLO text of the executable the most recent dispatch ran, as the
        backend compiled it — a Pallas kernel shows up as a
        ``tpu_custom_call`` on the chip."""
        if self._last_args is None or self._last_sig not in self._cache:
            raise ValueError("program has no live executable — dispatch it")
        fn, _ = self._cache[self._last_sig]
        return fn.lower(*self._last_args).compile().as_text()

    @property
    def plan_hash(self) -> str | None:
        """Stable digest of the most recently built plan (``None`` before
        the first build) — the cross-request cache identity the serving
        layer keys on."""
        return None if self.plan is None else self.plan.hash

    def reset_carry(self) -> None:
        """Reset per-shard carry state (error-feedback residuals and hash
        tables) to pristine for every built signature, WITHOUT dropping
        compiled executables.

        Long-lived owners — notably the serving layer — call this between
        logically independent queries that share one resident program, so
        one query's accumulated hash-table contents or residuals cannot
        leak into the next.  ``hash_result`` reflects only dispatches made
        since the most recent reset.
        """
        for key, plan in self._plans.items():
            self._residual_state[key] = tuple(
                jnp.zeros((self._n_shards,) + shape, dtype)
                for shape, dtype in plan.residual_specs
            )
            self._hash_state[key] = (
                list(plan.hash_targets),
                tuple(
                    (hm.table.keys, hm.table.vals, hm.table.overflow)
                    for hm in plan.hash_targets.values()
                ),
            )

    # -- fault supervision ----------------------------------------------------

    def degrade(self) -> int:
        """Degrade every live Pallas node of this program to eager.

        Called by the session supervisor on a kernel fault: the faulted
        nodes' ``tune_key``s go into the session's degraded set (so every
        later build — this program's, a per-op call's, or another
        program's — resolves them straight to eager) and the compiled
        executables are dropped so the next dispatch rebuilds.  Carry state
        (residuals, hash tables) survives the rebuild; the tuning cache is
        never touched.  Returns how many nodes were degraded.
        """
        degraded = getattr(self._session, "_degraded", None)
        if degraded is None:
            return 0
        n = 0
        for key, plan in self._plans.items():
            hit = False
            for node in plan.mapreduce_nodes():
                if (
                    node.engine == "pallas"
                    and not node.dead
                    and node.cse_of is None
                ):
                    degraded.add(node.tune_key)
                    hit = True
                    n += 1
            if hit:
                self._cache.pop(key, None)
                self._ran.discard(key)
        return n

    # -- carry export/restore (epoch-granular resume) -------------------------

    def export_carry(self, state) -> dict:
        """The program's cross-dispatch carry for ``state``'s signature, as
        a checkpointable pytree: error-feedback residuals and hash-target
        tables.  Together with the user state and the loop position this
        fully determines the remainder of a run — the resume payload of
        ``run_loop``/``run_stream``."""
        key = _mr._abstract(state)
        self._build(state)
        _hash_keys, hash_tuples = self._hash_state[key]
        return {
            "residual": list(self._residual_state[key]),
            "hash": [list(t) for t in hash_tuples],
        }

    def import_carry(self, state, carry: dict) -> None:
        """Overwrite the carry for ``state``'s signature with a previously
        exported (and checkpoint-restored) one."""
        key = _mr._abstract(state)
        self._build(state)
        self._residual_state[key] = tuple(carry["residual"])
        hash_keys, _old = self._hash_state[key]
        self._hash_state[key] = (
            hash_keys,
            tuple(tuple(t) for t in carry["hash"]),
        )

    def checkpoint_payload(self, state, pos: int) -> dict:
        """The full resume payload: user state + carry + position."""
        return {
            "state": state,
            "carry": self.export_carry(state),
            "pos": jnp.asarray(pos, jnp.int32),
        }

    def save_checkpoint(self, manager, state, pos: int) -> str:
        """Supervised checkpoint save: transient ``checkpoint.write`` faults
        are retried (bounded), fatal ones propagate."""
        payload = self.checkpoint_payload(state, pos)
        tries = 0
        while True:
            try:
                return manager.save(pos, payload)
            except faults.FatalFault as e:
                faults.record("fatal", e)
                raise
            except faults.TransientFault as e:
                tries += 1
                if tries >= 3:
                    faults.record("fatal", e)
                    raise
                faults.record("retried", e)

    def restore_checkpoint(self, manager, state):
        """Restore the latest checkpoint into ``(state, position)``; returns
        ``(state, None)`` when no checkpoint exists.  The carry is installed
        on this program as a side effect."""
        template = self.checkpoint_payload(state, 0)
        step, restored = manager.restore_latest(template)
        if step is None:
            return state, None
        state = restored["state"]
        self.import_carry(state, restored["carry"])
        return state, int(jax.device_get(restored["pos"]))

    # -- run -----------------------------------------------------------------

    def __call__(self, state, n_iters: int = 1, *, stream_blocks=None):
        """One dispatch: ``n_iters`` fused iterations, device-resident.

        Programs reading chunked (out-of-core) sources take the resident
        block per dispatch via ``stream_blocks`` — a dict mapping each
        stream-source key to its ``(data, base)`` device operands.  Use
        :meth:`run_stream` rather than passing this by hand.

        The host's part is a ``blaze.dispatch`` span (its seconds in
        ``SessionStats.dispatch_s``), or ``blaze.compile`` for the first
        call of a newly built executable, where jit compiles.
        """
        key = _mr._abstract(state)
        if key in self._ran:
            with tracing.span("dispatch", self._session.stats, "dispatch_s"):
                return self._dispatch(key, state, n_iters, stream_blocks)
        with tracing.span("compile") as sp:
            out = self._dispatch(key, state, n_iters, stream_blocks)
            sp.set_metadata(plan_hash=self.plan_hash)
        self._ran.add(key)
        return out

    def _dispatch(self, key, state, n_iters, stream_blocks):
        fn, operands = self._build(state)
        # Fault points fire BEFORE the executable runs or any carry is
        # written back, so a supervised retry of this dispatch is exact.
        faults.fault_point("dispatch")
        if self.plan is not None and faults.registry.armed:
            for node in self.plan.mapreduce_nodes():
                if node.engine != "pallas" or node.dead or node.cse_of is not None:
                    continue
                faults.fault_point(
                    "kernel.hash" if node.target_kind == "hash"
                    else "kernel.segment"
                )
        residuals = self._residual_state[key]
        hash_keys, hash_tuples = self._hash_state[key]
        flat_hash = [a for t in hash_tuples for a in t]
        stream_keys, _stream_sources = self._stream_state[key]
        if stream_keys and stream_blocks is None:
            raise ValueError(
                "program reads chunked (out-of-core) sources — drive it "
                "with program.run_stream(...) / session.run_stream(...)"
            )
        flat_stream = (
            [a for sk in stream_keys for a in stream_blocks[sk]]
            if stream_keys
            else []
        )
        args = (
            state, jnp.asarray(n_iters, jnp.int32), *residuals, *flat_hash,
            *flat_stream, *operands,
        )
        out, new_residuals, new_hash = fn(*args)
        self._residual_state[key] = new_residuals
        self._hash_state[key] = (hash_keys, tuple(new_hash))
        self._last_sig = key
        self._last_args = args
        self.stats.dispatches += 1
        self.stats.iterations += int(n_iters)
        self._session.stats.dispatches += 1
        self._session.stats.program_dispatches += 1
        return out

    def run_stream(
        self,
        state,
        *,
        max_epochs: int = 1,
        cond: Callable | None = None,
        prefetch: bool = True,
        depth: int = 2,
        checkpoint=None,
        checkpoint_every: int | None = None,
        resume: bool = False,
    ):
        """Out-of-core epochs: stream every block through ONE executable.

        One *epoch* dispatches the program once per block of its chunked
        source(s), in order — the step function sees one resident block per
        dispatch (global indices via the traced ``base`` offset) and carries
        its accumulation in ``state`` / hash-table state.  ``prefetch=True``
        produces block k+1 (disk read, decompress, host→device transfer) on
        a background thread while block k reduces — double-buffered, depth
        bounded by ``depth``.  ``prefetch=False`` is the synchronous
        baseline: each dispatch is drained (``block_until_ready``) before
        the next block is even read, i.e. zero compute/transfer overlap —
        the A/B the streaming benchmark measures.

        ``cond(state) -> bool`` is evaluated once per epoch (one host sync),
        mirroring ``run_loop``.  Returns ``(state, StreamInfo)``.

        Epoch-granular fault tolerance: with ``checkpoint=`` (a
        ``CheckpointManager`` or a directory) and ``checkpoint_every=K``,
        the user state + program carry + epoch position are saved every K
        completed epochs; ``resume=True`` restores the latest checkpoint and
        continues from its epoch — bit-equal to the uninterrupted run,
        because the carry and position fully determine the remainder (a
        crash mid-epoch replays that epoch from its boundary).  Per-block
        dispatches run under the session's retry policy, so transient
        injected faults are absorbed in place.
        """
        from repro.data.pipeline import prefetch_iter

        manager = _as_checkpoint_manager(checkpoint)
        if resume and manager is None:
            raise ValueError("resume=True needs checkpoint=")
        compiles0 = self.stats.compiles
        self._build(state)
        key = _mr._abstract(state)
        stream_keys, stream_sources = self._stream_state[key]
        if not stream_keys:
            raise ValueError(
                "program has no chunked sources — use run_loop/__call__"
            )
        counts = {src.n_blocks for src in stream_sources}
        if len(counts) != 1:
            raise ValueError(
                f"chunked sources disagree on block count: {sorted(counts)}"
            )
        n_blocks = counts.pop()
        mesh = self._mesh
        bytes_per_block = sum(src.block_nbytes for src in stream_sources)

        def produce(b):
            with tracing.span("feed.produce"):
                views = {}
                for sk, src in zip(stream_keys, stream_sources):
                    bv = src.block_view(b, mesh)
                    views[sk] = (bv.data, bv.base)
                return views

        resumed_from = None
        if resume:
            state, pos = self.restore_checkpoint(manager, state)
            if pos is not None:
                resumed_from = pos
        epochs = resumed_from or 0
        blocks = syncs = 0
        converged = False
        supervised = getattr(self._session, "supervised", None)
        stats = self._session.stats
        while epochs < max_epochs:
            if prefetch:
                it = prefetch_iter(produce, range(n_blocks), depth=depth)
            else:
                it = ((b, produce(b)) for b in range(n_blocks))
            while True:
                with tracing.span("feed.wait", stats, "feed_wait_s"):
                    nxt = next(it, None)
                if nxt is None:
                    break
                _b, views = nxt
                if supervised is not None:
                    state = supervised(
                        lambda: self(state, 1, stream_blocks=views),
                        program=self,
                    )
                else:
                    state = self(state, 1, stream_blocks=views)
                blocks += 1
                if not prefetch:
                    jax.block_until_ready(jax.tree_util.tree_leaves(state))
            epochs += 1
            if manager is not None and checkpoint_every:
                if epochs % checkpoint_every == 0:
                    self.save_checkpoint(manager, state, epochs)
            if cond is not None:
                stats.host_syncs += 1
                syncs += 1
                with tracing.span("sync"):
                    done = bool(cond(state))
                if done:
                    converged = True
                    break
        return state, StreamInfo(
            epochs=epochs,
            n_blocks=n_blocks,
            dispatches=blocks,
            host_syncs=syncs,
            converged=converged,
            compiles=self.stats.compiles - compiles0,
            prefetch=prefetch,
            bytes_streamed=blocks * bytes_per_block,
            resumed_from=resumed_from,
        )

    def hash_result(self, target: C.DistHashMap) -> C.DistHashMap:
        """The accumulated state of a hash target used by this program.

        ``target`` must be the same ``DistHashMap`` object the step function
        captured; the returned map holds the tables as of the most recent
        dispatch (the original object is never mutated).
        """
        tkey = ("hashtarget",) + _source_key("hashmap", target)[1:]
        sig = self._last_sig
        if sig is None or sig not in self._hash_state:
            raise ValueError("program has not dispatched yet")
        hash_keys, hash_tuples = self._hash_state[sig]
        if tkey not in hash_keys:
            raise KeyError(
                "not a hash target of this program (targets are keyed by "
                "the identity of their backing buffers)"
            )
        keys, vals, ovf = hash_tuples[hash_keys.index(tkey)]
        return C.DistHashMap(
            C.HashTable(keys, vals, ovf), reducer_name=target.reducer_name
        )
