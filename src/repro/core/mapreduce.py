"""Blaze MapReduce on SPMD JAX — eager reduction, compact wire, dense fast path.

``map_reduce(source, mapper, reducer, target)`` mirrors the paper's four-arg
functional API:

* **source** — ``DistRange`` | ``DistVector`` | ``DistHashMap`` |
  ``ChunkedDistVector`` (out-of-core: the session streams it one resident
  block at a time through ONE cached executable, prefetching block k+1
  while block k reduces — mappers still see global indices).
* **mapper** — paper-style emit-handler function, traced under ``vmap``:
    - ``DistRange``:   ``mapper(value, emit)``            (+ ``env`` if given)
    - ``DistVector``:  ``mapper(index, value, emit)``     (+ ``env`` if given)
    - ``DistHashMap``: ``mapper(key, value, emit)``       (+ ``env`` if given)
  ``emit(key, value, mask=True)`` may be called any static number of times;
  ``key``/``value`` may be scalars or 1-D batches (a line's worth of words),
  ``mask`` marks which emitted lanes are real.
* **reducer** — ``"sum" | "prod" | "min" | "max"`` or a custom ``Reducer``.
* **target** — a dense array of shape ``[K, ...]`` (the paper's small fixed
  key range / ``std::vector`` target: key == index) or a ``DistHashMap``.
  Per the paper, the target is *merged into*, never cleared.
* **env** — optional pytree of iteration-varying state (PageRank scores,
  k-means centroids, …) broadcast to every shard.  Keeping the mapper object
  static and threading state through ``env`` lets the engine reuse one
  compiled executable across iterations — executables are memoized per
  ``BlazeSession`` (see ``repro.core.session``), keyed on the abstract
  signature of everything that shapes the plan; the free ``map_reduce``
  routes through a process-wide default session.

Engines:

* ``engine="eager"`` (Blaze): duplicate keys are combined **on-device before
  any collective** (sort + segmented scan, or a dense ``[K]`` accumulator when
  the key range is small and fixed), then the shuffle moves locally-reduced
  data only — ``psum`` for dense targets, hash-partitioned ``all_to_all`` of
  unique pairs for hash targets.
* ``engine="pallas"`` (Blaze, kernel combine): the eager plan with every
  per-shard combine lowered through a Pallas kernel (interpret mode off-TPU).
  Dense targets run the segment-reduce kernel (``Reducer.pallas_segment`` —
  one-hot matmul on the MXU, VMEM-resident ``[K, V]`` accumulator); hash
  targets run the hash-aggregation kernel (``Reducer.pallas_hash`` — an
  open-addressing VMEM table that replaces both sort-based
  ``unique_combine`` passes *and* the ``hashmap_insert`` scatter loop).
  The static-key fast path and the shuffle collectives are identical to
  eager.  ``MapReduceStats`` additionally reports the kernel launch: block
  size, lane occupancy, and (hash) table capacity + probe depth.
* ``engine="naive"`` (conventional MapReduce / Spark's wide shuffle): every
  emitted pair goes on the wire unreduced; reduction happens only at the
  destination shard.
* ``engine="auto"``: resolved by the planner (``repro.core.plan``'s
  resolve-engines pass, applied per plan node) — pallas for built-in
  reducers whose accumulator (dense ``[K]`` / hash table) stays VMEM-sized,
  eager otherwise.

``wire`` ∈ {"none", "bf16", "int8"} applies the fast-serialization analogue to
the collective payload (dense-sum targets).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.compat import shard_map

from repro.core import containers as C
from repro.core import faults
from repro.core.plan import abstract_sig as _abstract, hier_collective_desc
from repro.core.reducers import Reducer, get_reducer
from repro.core.serialization import narrowest_int_dtype

Array = jax.Array


@dataclasses.dataclass
class MapReduceStats:
    """Wire accounting + runtime counters for one map_reduce call.

    Runtime fields hold device arrays until ``finalize()`` — the engine never
    blocks dispatch to materialise statistics.
    """

    engine: str
    collective: str  # which collective carried the shuffle
    pairs_emitted: Any  # live emitted pairs (device array until finalize)
    pairs_shipped: Any  # pairs that went on the wire post eager-combine
    shuffle_payload_bytes: Any  # bytes the shuffle moves (global, one call)
    # Topology split of the shuffle payload (combine-edge model): a reduce
    # over P participants has P-1 combine edges; hierarchical mode keeps
    # `n_shards - n_nodes` of them on fast intra-node links at FULL
    # precision and only `n_nodes - 1` on slow inter-node links at wire
    # precision, while a flat reduce on a multi-node mesh pays every edge
    # inter-node.  Both zero on 1-node meshes' inter side.
    intra_bytes: Any = 0  # bytes crossing intra-node links
    inter_bytes: Any = 0  # bytes crossing inter-node links
    overflow: Any = None  # hash-table / bucket drops
    compiles: int = 0  # 1 iff this call lowered+compiled a new executable
    cache_hits: int = 0  # 1 iff this call reused a session-cached executable
    dispatches: int = 1  # executable launches this call (always 1 standalone;
    #                      fused programs amortise N ops over one dispatch)
    # engine="pallas" only: the kernel's launch accounting (segment-reduce
    # for dense targets, hash-aggregation for DistHashMap targets).
    kernel_block_n: int | None = None  # pair-block size the kernel ran with
    kernel_lanes: int | None = None  # padded pair-lanes processed (global)
    kernel_pairs: Any = None  # live pairs entering the kernel (device array)
    kernel_occupancy: float | None = None  # kernel_pairs / kernel_lanes
    # hash-aggregation kernel only: table geometry + probe depth.
    kernel_table_cap: int | None = None  # pre-shuffle combine table capacity
    kernel_probe_depth: int | None = None  # configured max probe rounds
    # hash targets merged by hashmap_insert: the most probe rounds any
    # shard's merge ran (device array until finalize; None elsewhere).
    probe_rounds: Any = None
    # hash targets: lanes each shard buckets after its local combine
    # (static; pairs_shipped / (shuffle_lanes * n_shards) is their occupancy).
    shuffle_lanes: int | None = None
    # stable digest of this op's plan node (repro.core.plan) — identical for
    # the per-op and program spellings of the same op.
    plan_hash: str | None = None
    # supervised-dispatch provenance (repro.core.faults / session supervisor):
    # the engine this node was degraded FROM (None = never degraded), dispatch
    # retries absorbed, and hash-capacity escalations taken for this call.
    degraded_engine: str | None = None
    retries: int = 0
    escalations: int = 0

    def finalize(self) -> "MapReduceStats":
        def _get(x):
            if isinstance(x, (jax.Array, np.ndarray)):
                return int(np.asarray(jax.device_get(x)).sum())
            return x

        kernel_pairs = _get(self.kernel_pairs)
        occupancy = (
            kernel_pairs / self.kernel_lanes
            if self.kernel_lanes and kernel_pairs is not None
            else None
        )
        return MapReduceStats(
            engine=self.engine,
            collective=self.collective,
            pairs_emitted=_get(self.pairs_emitted),
            pairs_shipped=_get(self.pairs_shipped),
            shuffle_payload_bytes=_get(self.shuffle_payload_bytes),
            intra_bytes=_get(self.intra_bytes),
            inter_bytes=_get(self.inter_bytes),
            overflow=_get(self.overflow),
            compiles=self.compiles,
            cache_hits=self.cache_hits,
            dispatches=self.dispatches,
            kernel_block_n=self.kernel_block_n,
            kernel_lanes=self.kernel_lanes,
            kernel_pairs=kernel_pairs,
            kernel_occupancy=occupancy,
            kernel_table_cap=self.kernel_table_cap,
            kernel_probe_depth=self.kernel_probe_depth,
            probe_rounds=(
                None if self.probe_rounds is None
                else int(np.asarray(jax.device_get(self.probe_rounds)).max())
            ),
            shuffle_lanes=self.shuffle_lanes,
            plan_hash=self.plan_hash,
            degraded_engine=self.degraded_engine,
            retries=self.retries,
            escalations=self.escalations,
        )


class _Emitter:
    """Collects emit() calls during the vmapped mapper trace.

    Keys passed as Python ints are *static* (known at trace time): the dense
    engine then skips id arrays entirely and uses a fused whole-axis
    reduction — the paper's §2.3.3 per-thread scalar accumulator, at compile
    time.  (Monte-Carlo π's ``emit(0, …)``, PageRank's sink/delta sums and
    the GMM log-likelihood all take this path.)
    """

    def __init__(self):
        self.keys: list[Array] = []
        self.vals: list[Array] = []
        self.masks: list[Array] = []
        self.static_keys: list[int | None] = []

    def __call__(self, key, value, mask=True):
        static = int(key) if isinstance(key, (int, np.integer)) else None
        key = jnp.asarray(key, jnp.int32)
        value = jnp.asarray(value)
        mask = jnp.asarray(mask, bool)
        if key.ndim == 0:
            key = key[None]
        width = key.shape[0]
        if value.ndim == 0 or value.shape[:1] != (width,):
            value = jnp.broadcast_to(value, (width,) + value.shape)
        mask = jnp.broadcast_to(mask, (width,))
        self.keys.append(key)
        self.vals.append(value)
        self.masks.append(mask)
        self.static_keys.append(static)

    def structured(self):
        if not self.keys:
            raise ValueError("mapper emitted nothing (statically)")
        return tuple(zip(self.keys, self.vals, self.masks))


def _run_mapper_structured(
    source_kind, source_static, mapper, shard_idx, local, n_shards, env
):
    """vmap the emit-style mapper → (per-emit entries, static keys).

    entries: tuple of (keys [n,w], vals [n,w,...], mask [n,w]) per emit call;
    static_keys: per-emit Python int if the key was trace-time constant.
    """
    extra = (env,) if env is not None else ()
    meta: dict = {}

    def trace(*args):
        em = _Emitter()
        mapper(*args, em, *extra)
        meta["static"] = em.static_keys
        return em.structured()

    if source_kind == "range":
        values, valid = source_static.local_values(shard_idx, n_shards)
        entries = jax.vmap(trace)(values)
        elem_mask = valid
    elif source_kind == "vector":
        data, n_true = local
        per = data.shape[0]
        idx = jnp.arange(per) + shard_idx * per
        elem_mask = idx < n_true
        entries = jax.vmap(trace)(idx, data)
    elif source_kind == "chunked":
        # One block of an out-of-core dataset: ``base`` (traced) shifts this
        # shard's rows to their GLOBAL indices; ``idx < n_total`` masks both
        # last-block padding and shard padding, exactly like "vector".
        data, n_total, base = local
        per = data.shape[0]
        idx = base + jnp.arange(per) + shard_idx * per
        elem_mask = idx < n_total
        entries = jax.vmap(trace)(idx, data)
    elif source_kind == "hashmap":
        tkeys, tvals = local
        elem_mask = tkeys != C.EMPTY_KEY
        entries = jax.vmap(trace)(tkeys, tvals)
    else:
        raise TypeError(f"unsupported source kind {source_kind}")

    entries = [
        (k, v, m & elem_mask[:, None]) for (k, v, m) in entries
    ]
    return entries, meta["static"]


def _flatten_entries(entries):
    """Structured emits → flat (keys, vals, mask) arrays (shuffle paths)."""
    keys = jnp.concatenate([k.reshape(-1) for k, _, _ in entries])
    vals = jnp.concatenate(
        [v.reshape((-1,) + v.shape[2:]) for _, v, _ in entries], axis=0
    )
    masks = jnp.concatenate([m.reshape(-1) for _, _, m in entries])
    return keys, vals, masks


def _run_mapper(source_kind, source_static, mapper, shard_idx, local, n_shards, env):
    entries, _ = _run_mapper_structured(
        source_kind, source_static, mapper, shard_idx, local, n_shards, env
    )
    return _flatten_entries(entries)


# ---------------------------------------------------------------------------
# Shuffle plumbing: bucket pairs by destination shard, fixed capacity
# ---------------------------------------------------------------------------


def bucket_by_dest(
    keys: Array, vals: Array, valid: Array, n_dest: int, cap: int, ident
) -> tuple[Array, Array, Array]:
    """Pack pairs into a ``[n_dest, cap]`` buffer keyed by hash ownership.

    Returns (bkeys, bvals, n_dropped).  Position within a bucket is the pair's
    rank among same-destination pairs (stable sort + first-occurrence index) —
    fully vectorised, no host round-trip.
    """
    n = keys.shape[0]
    dest = jnp.where(valid, C.shard_of_key(keys, n_dest).astype(jnp.int32), n_dest)
    # Rank-within-bucket (and which pairs survive a full bucket) depends on
    # the sort preserving emission order among equal destinations — request
    # stability explicitly rather than relying on the backend default.
    order = jnp.argsort(dest, stable=True)
    sdest = jnp.take(dest, order)
    skeys = jnp.take(keys, order)
    svals = jnp.take(vals, order, axis=0)
    first = jnp.searchsorted(sdest, sdest, side="left")
    rank = jnp.arange(n) - first
    ok = (sdest < n_dest) & (rank < cap)
    flat = jnp.where(ok, sdest * cap + rank, n_dest * cap)
    bkeys = jnp.full((n_dest * cap,), C.EMPTY_KEY, jnp.int32)
    bkeys = bkeys.at[flat].set(jnp.where(ok, skeys, C.EMPTY_KEY), mode="drop")
    bvals = jnp.full((n_dest * cap,) + vals.shape[1:], ident, vals.dtype)
    bvals = bvals.at[flat].set(svals, mode="drop")
    dropped = jnp.sum((sdest < n_dest) & ~ok).astype(jnp.int32)
    return (
        bkeys.reshape(n_dest, cap),
        bvals.reshape((n_dest, cap) + vals.shape[1:]),
        dropped,
    )


# ---------------------------------------------------------------------------
# Collectives indirection
#
# A shard stage never names ``jax.lax`` collectives directly: it goes through
# a small collectives object, so the *same* stage body serves two tracing
# contexts —
#
# * ``RealCollectives``     — inside ``shard_map``, bound to the mesh axis;
# * ``AbstractCollectives`` — the program-discovery trace (``jax.eval_shape``
#   with no mesh axis in scope): shape-faithful local stand-ins, so a whole
#   iteration can be traced for structure before the fused executable exists.
# ---------------------------------------------------------------------------


class RealCollectives:
    """Mesh collectives bound to the data-parallel axes — valid inside
    ``shard_map``.

    ``axis`` is the fast intra-node axis; on a 2-D ``("node", "data")`` mesh
    ``node_axis``/``n_nodes`` describe the slow inter-node axis and flat
    collectives run over the ``(node, data)`` tuple (shard indices flatten
    node-major, matching the containers' leading-dim sharding).  ``reduce``
    and ``reduce_feedback`` additionally take ``hier=True``: intra-node
    reduction first at full precision, then only the node-level partials
    cross the inter-node hop (wire-compressed when requested) — routed
    through ``distributed.collectives``'s hierarchical entry points.
    """

    def __init__(
        self,
        axis: str,
        n_shards: int,
        *,
        node_axis: str | None = None,
        n_nodes: int = 1,
    ):
        self.axis = axis
        self.n_shards = n_shards
        self.node_axis = node_axis
        self.n_nodes = n_nodes
        self.all_axes = (node_axis, axis) if node_axis is not None else axis

    def _is_hier(self, hier: bool) -> bool:
        return bool(hier) and self.node_axis is not None and self.n_nodes > 1

    def axis_index(self) -> Array:
        return jax.lax.axis_index(self.all_axes)

    def all_gather_tiled(self, x: Array) -> Array:
        return jax.lax.all_gather(x, self.all_axes, tiled=True)

    def all_to_all_tiled(self, x: Array) -> Array:
        return jax.lax.all_to_all(
            x, self.all_axes, split_axis=0, concat_axis=0, tiled=True
        )

    def reduce(
        self, partial: Array, red: Reducer, wire: str, hier: bool = False
    ) -> Array:
        # Host code running during trace: an injected collective fault
        # surfaces as a compile-time failure of the dispatch that traced it.
        faults.fault_point("collective")
        if self._is_hier(hier):
            if wire != "none" and red.name == "sum":
                faults.fault_point("collective.inter")
                from repro.distributed.collectives import compressed_psum

                return compressed_psum(
                    partial, self.node_axis, wire=wire, intra_axis=self.axis
                )
            intra = _collective_reduce(partial, red, self.axis, "none")
            faults.fault_point("collective.inter")
            return _collective_reduce(intra, red, self.node_axis, wire)
        return _collective_reduce(partial, red, self.all_axes, wire)

    def reduce_feedback(
        self,
        partial: Array,
        red: Reducer,
        wire: str,
        residual: Array,
        hier: bool = False,
    ) -> tuple[Array, Array]:
        """``wire="int8"`` with error feedback (``quantize_with_feedback``).

        Quantizes ``partial + residual`` per 256-element block, psums the
        dequantized lattice (the wire payload a TPU lowering moves is the
        int8 blocks + scales, as in ``_collective_reduce``), and returns what
        this round's narrowing dropped as the next round's residual — the
        iterative path stays unbiased instead of accumulating rounding bias.

        Hierarchical mode folds the intra-node axis at full precision
        BEFORE quantisation, so only ``n_nodes`` addends (not ``n_shards``)
        pass through the int8 lattice and the residual tracks exactly the
        one lossy hop (every node member computes the same node-level
        residual — deterministic, no echo needed).
        """
        if wire != "int8" or red.name != "sum":
            return self.reduce(partial, red, wire, hier=hier), residual
        from repro.core.serialization import dequantize, quantize_with_feedback

        p32 = partial.astype(jnp.float32)
        axes = self.all_axes
        if self._is_hier(hier):
            p32 = jax.lax.psum(p32, self.axis)  # full-precision intra hop
            faults.fault_point("collective.inter")
            axes = self.node_axis
        q, new_residual = quantize_with_feedback(p32, residual, "int8")
        deq = dequantize(q, p32)
        total = jax.lax.psum(deq, axes).astype(partial.dtype)
        return total, new_residual


class AbstractCollectives:
    """Shape-faithful stand-ins for the discovery trace (no mesh axis bound).

    Every per-shard reduction collective (``psum``/``pmin``/``pmax``, the
    gather-fold of ``prod`` and custom reducers) preserves shape, so identity
    is a faithful abstraction; ``all_gather(tiled)`` concatenates
    ``n_shards`` copies; ``all_to_all(tiled)`` over equal splits is
    shape-preserving.  Values computed under these are never used — only
    their shapes/dtypes (``jax.eval_shape``) and the op-recording side
    effects of the trace.  The hierarchical flag is shape-invisible, so
    both modes share one abstraction.
    """

    def __init__(self, n_shards: int, *, n_nodes: int = 1):
        self.n_shards = n_shards
        self.n_nodes = n_nodes

    def axis_index(self) -> Array:
        return jnp.zeros((), jnp.int32)

    def all_gather_tiled(self, x: Array) -> Array:
        return jnp.concatenate([x] * self.n_shards, axis=0)

    def all_to_all_tiled(self, x: Array) -> Array:
        return x

    def reduce(
        self, partial: Array, red: Reducer, wire: str, hier: bool = False
    ) -> Array:
        return partial

    def reduce_feedback(self, partial, red, wire, residual, hier=False):
        return partial, residual


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------


def _source_kind(source) -> str:
    if isinstance(source, C.DistRange):
        return "range"
    if isinstance(source, C.DistVector):
        return "vector"
    if isinstance(source, C.DistHashMap):
        return "hashmap"
    if isinstance(source, (C.ChunkedDistVector, C.BlockView)):
        return "chunked"
    raise TypeError(f"unsupported source {type(source)}")


def map_reduce(
    source,
    mapper: Callable,
    reducer: str | Reducer,
    target,
    *,
    mesh: Mesh | None = None,
    engine: str = "eager",
    wire: str = "none",
    env: Any = None,
    key_range: int | None = None,
    return_stats: bool = False,
    session=None,
):
    """The paper's four-arg functional API, as a thin session wrapper.

    Routes through ``session`` (or the process-wide default ``BlazeSession``),
    which owns the mesh and the compiled-executable cache — N iterative calls
    with the same (source spec, mapper, reducer, target spec, engine, wire)
    compile exactly once.  See ``repro.core.session``.  ``key_range`` (hash
    targets: keys promised to lie in ``[0, key_range)``) narrows the shuffle
    key dtype and sizes the pallas combine table.
    """
    from repro.core.session import get_default_session

    sess = session if session is not None else get_default_session()
    return sess.map_reduce(
        source, mapper, reducer, target, mesh=mesh, engine=engine, wire=wire,
        env=env, key_range=key_range,
        return_stats=return_stats,
    )


def _source_operands(kind, source, mesh=None):
    """(device operands, in_specs) for shard_map, per source kind.

    For ``kind="chunked"`` the dispatch-time source is a ``BlockView``
    (one resident block): data sharded over ``data`` plus the replicated
    traced ``base`` offset — per-block values vary, abstract signature
    doesn't, so every block reuses one executable.  Specs shard over every
    data-parallel mesh axis (``node`` and ``data`` on 2-D meshes).
    """
    d = C.data_pspec(mesh) if mesh is not None else P(C.DATA_AXIS)
    if kind == "range":
        return (), ()
    if kind == "vector":
        return (source.data,), (d,)
    if kind == "chunked":
        return (source.data, source.base), (d, P())
    return (source.table.keys, source.table.vals), (d, d)


def _local_view(kind, source, operands):
    if kind == "range":
        return None
    if kind == "vector":
        return (operands[0], source.n)
    if kind == "chunked":
        return (operands[0], source.n, operands[1])
    return (operands[0][0], operands[1][0])


# Dense targets with at most this many keys combine by a fused one-hot
# reduction rather than XLA's scatter.  A scatter folds every duplicate of a
# key serially into one accumulator (an f32 count stops growing at 2**24)
# and, for a narrow ``[N, V]`` value row, asks the TPU for a row-major copy
# padded to 128 lanes — 21x the bytes at V=6.  The one-hot reduction reads
# the pairs once, in whatever layout XLA picked, and sums them as a tree.
ONEHOT_MAX_KEYS = 64


def dense_segment(red: Reducer, vals: Array, ids: Array, k: int) -> Array:
    """Reduce ``vals [N, ...]`` by ``ids [N]`` into a dense ``[k, ...]``
    (XLA, no kernel); ids outside ``[0, k)`` are dropped."""
    if k <= ONEHOT_MAX_KEYS and red.axis_reduce is not None:
        hit = ids[:, None] == jnp.arange(k, dtype=ids.dtype)  # [N, k]
        hit = hit.reshape(hit.shape + (1,) * (vals.ndim - 1))
        ident = red.identity(vals.dtype)
        return red.axis_reduce(jnp.where(hit, vals[:, None], ident), axis=0)
    safe = jnp.where((ids >= 0) & (ids < k), ids, k)
    return red.segment(vals, safe, k + 1)[:k]


def dense_shard_stage(
    kind, source, mapper, red, target, engine, wire, n_shards,
    with_stats=True, feedback=False, collect=True, hier=False,
):
    """Build a pure, composable shard stage for a dense ``[K, ...]`` target.

    The stage is the whole per-shard plan — mapper trace, local combine
    (static-key fast path / segmented reduce / Pallas kernel), and the
    shuffle collective — as a *function*, not a sealed ``jit(shard_map(...))``:

        ``stage(env, local, coll, residual=None)
            -> (total, live, kernel_pairs, residual')``

    * ``env``      — the iteration-varying pytree (broadcast, replicated);
    * ``local``    — this shard's operand view (``_local_view``), or a
      program-supplied local vector;
    * ``coll``     — a collectives object (``RealCollectives`` inside
      ``shard_map``, ``AbstractCollectives`` under program discovery);
    * ``residual`` — per-shard error-feedback carry when ``feedback=True``
      (``wire="int8"`` sums in an iterative program), else passed through.

    ``hier=True`` (multi-node meshes, set by the plan layer's
    ``hierarchical-collectives`` pass) makes the stage's collective
    topology-aware: intra-node reduce first at full precision, wire
    narrowing only on the inter-node hop (``RealCollectives.reduce``).

    ``collect=False`` (eager/pallas only) makes the stage stop at the
    per-shard PARTIAL: ``total`` comes back *unreduced* and the caller owns
    the collective.  This is the seam the plan optimizer's
    ``batch-collectives`` pass rides — a program flushes several pending
    partials through ONE concatenated collective (``repro.core.program``).

    ``total`` is the merged (replicated) dense result *excluding* the target
    — callers fold it in with ``red.combine(target, total)``.  Standalone
    ``map_reduce`` wraps one stage in ``shard_map`` + ``jit``
    (``_map_reduce_dense``); ``repro.core.program`` composes several stages
    plus elementwise glue inside ONE ``shard_map`` body, which is what lets
    a whole iteration fuse into a single executable.

    Returns ``(stage, kernel_meta)``; ``kernel_meta`` is filled at trace time
    with the Pallas launch geometry (``block_n``, ``lanes``) when the kernel
    runs.
    """
    K = target.shape[0]
    target_dtype = target.dtype
    kernel_meta: dict = {}

    def stage(env_, local, coll, residual=None):
        entries, static_keys = _run_mapper_structured(
            kind, source, mapper, coll.axis_index(), local, n_shards, env_
        )
        live = (
            sum(jnp.sum(m) for _, _, m in entries).astype(jnp.int32)
            if with_stats or engine == "naive"
            else jnp.zeros((), jnp.int32)
        )
        kernel_pairs = jnp.zeros((), jnp.int32)

        if engine in ("eager", "pallas"):
            # §2.3.3 static-key fast path: trace-time-constant keys get a
            # fused whole-axis reduction — no id arrays, the exact plan a
            # hand-written parallel-for emits.  (Shared by both engines:
            # a kernel cannot beat a fused scalar reduction.)
            val_shape = entries[0][1].shape[2:]
            ident = red.identity(target_dtype)
            partial = jnp.full((K,) + val_shape, ident, target_dtype)
            dynamic = []
            for (keys, vals, mask), sk in zip(entries, static_keys):
                vals = vals.astype(target_dtype)
                if (
                    sk is not None
                    and 0 <= sk < K
                    and red.axis_reduce is not None
                ):
                    mb = mask.reshape(mask.shape + (1,) * len(val_shape))
                    contrib = red.axis_reduce(
                        jnp.where(mb, vals, ident), axis=(0, 1)
                    )
                    partial = partial.at[sk].set(
                        red.combine(partial[sk], contrib)
                    )
                else:
                    dynamic.append((keys, vals, mask))
            if dynamic:
                dkeys, dvals, dmask = _flatten_entries(dynamic)
                dvals = dvals.astype(target_dtype)
                if engine == "pallas" and red.pallas_segment is not None:
                    # Device-local combine on the MXU: invalid lanes get
                    # id −1, which the kernel drops (their values never
                    # reach the accumulator, so no masking of dvals).
                    ids = jnp.where(
                        dmask & (dkeys >= 0) & (dkeys < K), dkeys, -1
                    )
                    flat = dvals.reshape((dvals.shape[0], -1))
                    seg = red.pallas_segment(ids, flat, K)
                    seg = seg.reshape((K,) + dvals.shape[1:])
                    from repro.kernels.segment_reduce import (
                        segment_reduce_lanes,
                    )

                    bn, lanes = segment_reduce_lanes(
                        flat.shape[0], K, flat.shape[1], red.name, flat.dtype
                    )
                    kernel_meta["block_n"] = bn
                    kernel_meta["lanes"] = lanes * n_shards
                    kernel_pairs = jnp.sum(
                        dmask & (dkeys >= 0) & (dkeys < K)
                    ).astype(jnp.int32)
                else:
                    # eager, or a custom reducer without a kernel impl.
                    seg = dense_segment(red, dvals, jnp.where(dmask, dkeys, -1), K)
                partial = red.combine(partial, seg.astype(target_dtype))
            if not collect:
                total = partial  # caller runs the (possibly batched) collective
            elif feedback:
                total, residual = coll.reduce_feedback(
                    partial, red, wire, residual, hier=hier
                )
            else:
                total = coll.reduce(partial, red, wire, hier=hier)
        else:
            # Conventional plan: ship ALL raw pairs (padded lanes and all);
            # reduce only at the destination.  all_gather of the raw pair
            # stream is the dense-target equivalent of a wide shuffle.
            keys, vals, valid = _flatten_entries(entries)
            vals = vals.astype(target_dtype)
            gk = coll.all_gather_tiled(keys)
            gv = coll.all_gather_tiled(vals)
            gm = coll.all_gather_tiled(valid)
            total = dense_segment(red, gv, jnp.where(gm, gk, -1), K)
        return total, live, kernel_pairs, residual

    return stage, kernel_meta


def make_collectives(mesh, n_shards: int) -> "RealCollectives":
    """The mesh's ``RealCollectives`` (topology-aware on 2-D meshes)."""
    nodes = C.n_nodes(mesh)
    return RealCollectives(
        C.DATA_AXIS,
        n_shards,
        node_axis=C.NODE_AXIS if nodes > 1 else None,
        n_nodes=nodes,
    )


def reduce_edge_bytes(
    n_elems: int,
    full_bytes: int,
    wire_val_bytes: int,
    n_shards: int,
    n_nodes: int,
    hier: bool,
) -> tuple[int, int]:
    """(intra_bytes, inter_bytes) of one dense reduction, combine-edge model.

    A reduction over P participants moves P-1 combine edges.  Hierarchical
    mode keeps ``n_shards - n_nodes`` edges intra-node at FULL element width
    and ``n_nodes - 1`` inter-node at wire width; a flat reduce on a
    multi-node mesh is topology-oblivious and pays every edge inter-node at
    wire width; on a 1-node mesh everything is intra and inter is 0.
    """
    if n_nodes > 1 and hier:
        intra = n_elems * full_bytes * (n_shards - n_nodes)
        inter = n_elems * wire_val_bytes * (n_nodes - 1)
    elif n_nodes > 1:
        intra = 0
        inter = n_elems * wire_val_bytes * (n_shards - 1)
    else:
        intra = n_elems * wire_val_bytes * (n_shards - 1)
        inter = 0
    return intra, inter


def _map_reduce_dense(
    kind, source, mapper, red, target, mesh, n_shards, engine, wire, env,
    with_stats=True, cache=None, node=None, hier=False,
):
    """Dense [K, ...] target — the paper's small fixed key range fast path."""
    K = target.shape[0]
    cache = cache if cache is not None else {}
    if engine not in ("eager", "pallas", "naive"):
        raise ValueError(f"unknown engine {engine!r}")
    nodes = C.n_nodes(mesh)
    hier = bool(hier) and nodes > 1 and engine in ("eager", "pallas")

    # The executable cache key IS the plan node's identity-faithful cache
    # signature: everything that shapes the lowered plan, with the mapper and
    # reducer kept by object (two lambdas with one qualname stay distinct).
    cache_key = (
        "dense", mapper, red.name, red, engine, wire, mesh, kind, with_stats,
        _abstract(_source_operands(kind, source)[0]),
        getattr(source, "n", None) if kind in ("vector", "chunked") else
        (source.start, source.stop, source.step) if kind == "range" else None,
        _abstract(target), _abstract(env),
    ) + (("hier",) if hier else ())
    if node is not None:
        node.cache_sig = cache_key

    compiled_now = cache_key not in cache
    if compiled_now:
        stage, kernel_meta = dense_shard_stage(
            kind, source, mapper, red, target, engine, wire, n_shards,
            with_stats=with_stats, hier=hier,
        )
        d = C.data_pspec(mesh)

        def shard_fn(env_, *operands):
            coll = make_collectives(mesh, n_shards)
            local = _local_view(kind, source, operands)
            total, live, kernel_pairs, _ = stage(env_, local, coll)
            return total, live[None], kernel_pairs[None]

        fn = shard_map(
            shard_fn,
            mesh=mesh,
            in_specs=(P(),) + tuple(_source_operands(kind, source, mesh)[1]),
            out_specs=(P(), d, d),
            check_vma=False,
        )

        def run(env_, target_, *operands):
            total, live, kpairs = fn(env_, *operands)
            return red.combine(target_, total.astype(target_.dtype)), live, kpairs

        cache[cache_key] = (jax.jit(run), kernel_meta)

    run_fn, kernel_meta = cache[cache_key]
    operands, _ = _source_operands(kind, source)
    faults.fault_point("dispatch")
    if engine == "pallas":
        faults.fault_point("kernel.segment")
    merged, live, kernel_pairs = run_fn(env, target, *operands)

    val_bytes = {"bf16": 2, "int8": 1}.get(wire, jnp.dtype(target.dtype).itemsize)
    full_bytes = jnp.dtype(target.dtype).itemsize
    key_bytes = narrowest_int_dtype(K).itemsize
    n_elems = int(np.prod(target.shape))
    if engine in ("eager", "pallas"):
        payload = n_elems * val_bytes * n_shards
        coll = (
            hier_collective_desc(red.name, wire)
            if hier
            else f"psum[{K}x{val_bytes}B]"
        )
        shipped = n_elems * n_shards
        intra_b, inter_b = reduce_edge_bytes(
            n_elems, full_bytes, val_bytes, n_shards, nodes, hier
        )
    else:
        payload = live  # finalized below: pairs * (key+val) bytes
        coll = f"all_gather[pairs x {key_bytes + val_bytes}B]"
        shipped = live
        intra_b = inter_b = 0  # replaced below once live pairs are known
    stats = MapReduceStats(
        engine=engine,
        collective=coll,
        pairs_emitted=live,
        pairs_shipped=shipped,
        shuffle_payload_bytes=payload,
        intra_bytes=intra_b,
        inter_bytes=inter_b,
        compiles=int(compiled_now),
        cache_hits=int(not compiled_now),
        kernel_block_n=kernel_meta.get("block_n"),
        kernel_lanes=kernel_meta.get("lanes"),
        kernel_pairs=kernel_pairs if kernel_meta else None,
        plan_hash=node.hash if node is not None else None,
    )
    if engine == "naive":
        naive_payload = jnp.sum(live) * (key_bytes + val_bytes) * n_shards
        # all_gather edges: every shard's pairs reach all n_shards-1 peers;
        # with per-node rows of n_shards/nodes shards, the inter fraction of
        # peer links is (n_shards - n_shards/nodes) / (n_shards - 1).
        if nodes > 1 and n_shards > 1:
            inter_frac = (n_shards - n_shards // nodes) / (n_shards - 1)
        else:
            inter_frac = 0.0
        stats = dataclasses.replace(
            stats,
            shuffle_payload_bytes=naive_payload,
            intra_bytes=naive_payload * (1.0 - inter_frac),
            inter_bytes=naive_payload * inter_frac,
        )
    return merged, stats


def _collective_reduce(partial: Array, red: Reducer, axis, wire: str) -> Array:
    """One reduction hop over ``axis`` (a name or tuple of names).

    Narrowed sums route through ``distributed.collectives.compressed_psum``
    (shared-scale int8 over the int8 lattice / bf16 cast — see there); every
    other (reducer, wire) pair is the reducer's own collective.
    """
    if wire == "none" or red.name != "sum":
        return red.collective(partial, axis)
    if wire not in ("bf16", "int8"):
        raise ValueError(f"unknown wire mode {wire!r}")
    from repro.distributed.collectives import compressed_psum

    return compressed_psum(partial, axis, wire=wire)


def _wire_key_dtype(key_range: int | None) -> jnp.dtype:
    """Key dtype the hash shuffle ships: narrowed when the range is known
    (the §2.3.2 fast-serialization analogue for *explicit* keys)."""
    if key_range is None:
        return jnp.dtype(jnp.int32)
    return narrowest_int_dtype(key_range)


# Each shard's bucket for one destination holds this many times its fair
# share of the stream's lanes (``n_emit / n_shards``); pairs past it are
# dropped into ``overflow``.
BUCKET_SLACK = 2.0


def hash_shard_stage(
    kind, source, mapper, red, val_dtype, engine, n_shards, key_range=None,
):
    """Build the composable shard stage for a ``DistHashMap`` target.

    Same contract as ``dense_shard_stage`` — the whole per-shard plan
    (mapper trace, eager local combine, destination bucketing, ``all_to_all``
    shuffle, table merge) as a pure function of this shard's inputs:

        ``stage(env, table, local, coll)
            -> (table', live_emitted, live_shipped, kernel_pairs, rounds)``

    ``table`` is this shard's ``HashTable``; the returned table has the
    shuffled pairs merged in and bucket drops added to ``overflow``;
    ``rounds`` is the probe rounds ``hashmap_insert`` ran to merge them
    (0 where the kernel merged).

    * ``engine="eager"`` combines locally with the sort-based
      ``unique_combine`` before the shuffle and merges received pairs with a
      second ``unique_combine`` + ``hashmap_insert`` scatter loop, which
      stops once every received key is placed.
    * ``engine="pallas"`` lowers BOTH combines through the hash-aggregation
      kernel (``repro.kernels.hash_combine``): the pre-shuffle combine
      streams raw pairs into a fresh VMEM-resident table (duplicates fold
      in-kernel — no sort), and the post-shuffle merge streams received
      pairs straight into the target shard's table (``init=``), replacing
      the ``unique_combine`` + ``hashmap_insert`` pair.
    * ``engine="naive"`` ships every raw pair and reduces at the
      destination only.

    ``key_range`` (keys known to lie in ``[0, key_range)``) narrows the
    bucket-key dtype on the wire and sizes the kernel's combine table by the
    distinct-key bound instead of the stream length.  On the eager path it
    also bounds the combined pairs: ``unique_combine``'s ``n_emit`` lanes
    are compacted (``compact_live``) to ``min(n_emit, key_range)`` before
    bucketing, so the bucketing, the shuffle and the merge run on those
    lanes; live pairs beyond them (a broken promise) are counted into
    ``overflow`` like bucket drops.  The merge keeps the probe depth of the
    uncompacted buckets, so its table and rounds are those it would give
    without the compaction.

    Standalone ``map_reduce`` wraps one stage in ``shard_map`` + ``jit``
    (``_map_reduce_hash``); ``repro.core.program`` composes it into fused
    iteration bodies with the shard's table threaded through the loop carry.
    Returns ``(stage, meta)`` — ``meta`` is filled at trace time with
    ``shuffle_lanes`` (the lanes each shard buckets) and, when the kernel
    runs, its launch geometry.
    """
    from repro.kernels import hash_combine as HK

    use_kernel = engine == "pallas" and red.pallas_hash is not None
    meta: dict = {}

    def stage(env_, table, local, coll):
        keys, vals, valid = _run_mapper(
            kind, source, mapper, coll.axis_index(), local, n_shards, env_
        )
        vals = vals.astype(val_dtype)
        n_emit = keys.shape[0]
        live_emitted = jnp.sum(valid).astype(jnp.int32)
        kernel_pairs = jnp.zeros((), jnp.int32)
        pre_drop = jnp.zeros((), jnp.int32)
        rounds = jnp.zeros((), jnp.int32)

        if use_kernel:
            # Kernel local combine: raw pairs → fresh VMEM hash table.  The
            # table's live rows *are* the locally-reduced pairs (at most one
            # per key), so the sort-based unique_combine disappears.
            vflat = vals.reshape((n_emit, -1))
            cap, bn, probes = HK.choose_table_cap(
                n_emit, vflat.shape[1], red.name, vflat.dtype,
                distinct_hint=key_range,
            )
            mkeys = jnp.where(valid, keys, HK.EMPTY_KEY)
            tk, tv, pre_drop = red.pallas_hash(
                mkeys, vflat, cap, max_probes=probes, block_n=bn
            )
            keys, valid = tk, tk != HK.EMPTY_KEY
            vals = tv.reshape((cap,) + vals.shape[1:]).astype(val_dtype)
            kernel_pairs = live_emitted
            _, lanes = HK.hash_aggregate_lanes(
                n_emit, cap, vflat.shape[1], red.name, vflat.dtype,
                block_n=bn,
            )
            meta.update(
                block_n=bn, lanes=lanes * n_shards, table_cap=cap,
                probe_depth=probes,
            )
        ident = red.identity(vals.dtype)
        bucket_cap = max(1, int(math.ceil(BUCKET_SLACK * n_emit / n_shards)))
        # The merge's probe depth is sized from the lanes it would receive
        # without the compaction below: compacting changes no table or round.
        merge_lanes = n_shards * min(bucket_cap, keys.shape[0])
        if engine == "eager":
            keys, vals, valid = C.unique_combine(keys, vals, valid, red)
            if key_range is not None and key_range < n_emit:
                keys, vals, valid, pre_drop = C.compact_live(
                    keys, vals, valid, key_range, ident
                )
        live_shipped = jnp.sum(valid).astype(jnp.int32)

        n_stream = keys.shape[0]
        meta["shuffle_lanes"] = n_stream
        bucket_cap = min(bucket_cap, n_stream)
        bkeys, bvals, dropped = bucket_by_dest(
            keys, vals, valid, n_shards, bucket_cap, ident
        )
        # Narrowed keys on the wire: the shuffle ships the smallest int
        # dtype covering [0, key_range); EMPTY_KEY maps to the narrow
        # dtype's own min sentinel and back.
        wire_dtype = _wire_key_dtype(key_range)
        if wire_dtype.itemsize < 4:
            sentinel = int(jnp.iinfo(wire_dtype).min)
            nk = jnp.where(bkeys == C.EMPTY_KEY, sentinel, bkeys)
            rk = coll.all_to_all_tiled(nk.astype(wire_dtype))
            rkeys = rk.astype(jnp.int32).reshape(-1)
            rkeys = jnp.where(rkeys == sentinel, C.EMPTY_KEY, rkeys)
        else:
            rkeys = coll.all_to_all_tiled(bkeys).reshape(-1)
        rvals = coll.all_to_all_tiled(bvals)
        rvals = rvals.reshape((-1,) + rvals.shape[2:])
        rvalid = rkeys != C.EMPTY_KEY
        table = C.HashTable(
            table.keys, table.vals, table.overflow + dropped + pre_drop
        )
        if use_kernel:
            # Kernel merge into the target shard's table: received pairs may
            # repeat across source shards, and the kernel folds duplicates
            # natively — the second unique_combine and the hashmap_insert
            # scatter loop both disappear.
            n_recv = rkeys.shape[0]
            mk = jnp.where(rvalid, rkeys, HK.EMPTY_KEY)
            rflat = rvals.astype(val_dtype).reshape((n_recv, -1))
            merge_probes = max(16, HK.choose_probe_depth(n_recv, table.capacity))
            tk, tv, ovf = red.pallas_hash(
                mk, rflat, table.capacity,
                init=(
                    table.keys,
                    table.vals.reshape((table.capacity, -1)),
                    table.overflow,
                ),
                max_probes=merge_probes,
            )
            table = C.HashTable(
                tk, tv.reshape(table.vals.shape).astype(val_dtype), ovf
            )
            meta.setdefault("merge_probe_depth", merge_probes)
        else:
            ukeys, uvals, uvalid = C.unique_combine(rkeys, rvals, rvalid, red)
            # Same adaptive probe depth as the kernel merge: near-capacity
            # tables need more rounds to *find* the free slots that exist.
            merge_probes = max(
                16, HK.choose_probe_depth(merge_lanes, table.capacity)
            )
            table, rounds = C.hashmap_insert_rounds(
                table, ukeys, uvals, uvalid, red, max_probes=merge_probes
            )
        return table, live_emitted, live_shipped, kernel_pairs, rounds

    return stage, meta


def _map_reduce_hash(
    kind, source, mapper, red, target, mesh, n_shards, engine, env,
    key_range=None, cache=None, node=None,
):
    """DistHashMap target: local combine → hash-partition → all_to_all → merge."""
    cache = cache if cache is not None else {}
    nodes = C.n_nodes(mesh)

    cache_key = (
        "hash", mapper, red.name, red, engine, mesh, kind, key_range,
        _abstract(_source_operands(kind, source)[0]),
        getattr(source, "n", None) if kind in ("vector", "chunked") else
        (source.start, source.stop, source.step) if kind == "range" else None,
        _abstract((target.table.keys, target.table.vals)), _abstract(env),
    )
    if node is not None:
        node.cache_sig = cache_key

    compiled_now = cache_key not in cache
    if compiled_now:
        stage, meta = hash_shard_stage(
            kind, source, mapper, red, target.table.vals.dtype, engine,
            n_shards, key_range=key_range,
        )

        def shard_fn(env_, tkeys, tvals, tovf, *operands):
            coll = make_collectives(mesh, n_shards)
            local = _local_view(kind, source, operands)
            table = C.HashTable(tkeys[0], tvals[0], tovf[0])
            table, live_emitted, live_shipped, kernel_pairs, rounds = stage(
                env_, table, local, coll
            )
            return (
                table.keys[None],
                table.vals[None],
                table.overflow[None],
                live_emitted[None],
                live_shipped[None],
                kernel_pairs[None],
                rounds[None],
            )

        d = C.data_pspec(mesh)
        in_specs = (P(), d, d, d) + tuple(_source_operands(kind, source, mesh)[1])
        cache[cache_key] = (
            jax.jit(
                shard_map(
                    shard_fn,
                    mesh=mesh,
                    in_specs=in_specs,
                    out_specs=(d, d, d, d, d, d, d),
                    check_vma=False,
                )
            ),
            meta,
        )

    run_fn, meta = cache[cache_key]
    operands, _ = _source_operands(kind, source)
    faults.fault_point("dispatch")
    if engine == "pallas":
        faults.fault_point("kernel.hash")
    nk, nv, novf, emitted, shipped, kernel_pairs, rounds = run_fn(
        env, target.table.keys, target.table.vals, target.table.overflow, *operands
    )
    out = C.DistHashMap(C.HashTable(nk, nv, novf), reducer_name=red.name)
    val_bytes = jnp.dtype(target.table.vals.dtype).itemsize
    key_bytes = _wire_key_dtype(key_range).itemsize
    payload = jnp.sum(shipped) * (key_bytes + val_bytes)
    # all_to_all is point-to-point: with hash-uniform destinations, the
    # fraction of pairs leaving their node row is (n_shards - n_data)/n_shards
    # — no hierarchical rewrite applies, only honest topology accounting.
    inter_frac = (
        (n_shards - n_shards // nodes) / n_shards
        if nodes > 1 and n_shards > 1
        else 0.0
    )
    stats = MapReduceStats(
        engine=engine,
        collective=f"all_to_all[pairs x {key_bytes + val_bytes}B]",
        pairs_emitted=emitted,
        pairs_shipped=shipped,
        shuffle_payload_bytes=payload,
        intra_bytes=payload * (1.0 - inter_frac),
        inter_bytes=payload * inter_frac,
        overflow=novf,
        compiles=int(compiled_now),
        cache_hits=int(not compiled_now),
        kernel_block_n=meta.get("block_n"),
        kernel_lanes=meta.get("lanes"),
        kernel_pairs=kernel_pairs if "lanes" in meta else None,
        kernel_table_cap=meta.get("table_cap"),
        kernel_probe_depth=meta.get("probe_depth"),
        probe_rounds=None if "merge_probe_depth" in meta else rounds,
        shuffle_lanes=meta["shuffle_lanes"],
        plan_hash=node.hash if node is not None else None,
    )
    return out, stats
