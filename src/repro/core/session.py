"""BlazeSession — the long-lived driver context for iterative MapReduce.

The paper's wins on iterative data mining (PageRank, k-means, GMM/EM) come
from keeping the hot loop resident: pay lowering + compilation once per
(algorithm, shape) configuration, then run N iterations that only dispatch.
``BlazeSession`` is the seam that makes this true and observable:

* it **owns the mesh** — one 1-D ``data`` mesh per session by default, shared
  by every ``map_reduce`` it runs;
* it **memoizes compiled executables**, keyed on (source container spec,
  mapper identity, reducer, target spec, engine, wire, env spec) — the same
  key the engine builds in ``repro.core.mapreduce``.  Iteration-varying state
  (scores, centroids, mixture parameters) must flow through ``env`` so the
  key, and therefore the executable, stays fixed across iterations;
* it **counts compiles and cache hits** — cumulatively in ``session.stats``
  and per call in ``MapReduceStats.compiles`` / ``.cache_hits`` — so "10
  iterations, 1 compile per configuration" is an assertable property, not a
  docstring promise (see ``tests/test_session.py``).

The free function ``repro.core.map_reduce`` is a thin wrapper over a lazily
created process-wide default session, so existing one-shot code keeps
working; iterative drivers take an optional ``session=`` and algorithms
create/receive one explicitly.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.core import containers as C
from repro.core import cost as cost_mod
from repro.core import faults
from repro.core import mapreduce as _mr
from repro.core import plan as plan_mod
from repro.core import tracing
# The engine-resolution policy moved to repro.core.plan in PR 5 (it is the
# plan optimizer's resolve-engines pass, applied per node); these re-exports
# keep the long-standing session spellings working.
from repro.core.plan import ENGINES, PALLAS_AUTO_MAX_KEYS, resolve_engine
from repro.core.reducers import Reducer, get_reducer

__all__ = [
    "BlazeSession",
    "ENGINES",
    "PALLAS_AUTO_MAX_KEYS",
    "SessionStats",
    "get_default_session",
    "reset_default_session",
    "resolve",
    "resolve_engine",
    "set_default_session",
]


@dataclasses.dataclass
class SessionStats:
    """Cumulative executable-reuse + dispatch/sync counters for one session.

    ``dispatches`` and ``host_syncs`` make the fusion contract assertable:
    N per-op iterations cost ~3–4 dispatches and 1 host sync *each*, while
    ``run_loop`` over a fused program costs ≤ ⌈N/unroll⌉ of both.
    """

    calls: int = 0  # map_reduce invocations routed through the session
    compiles: int = 0  # calls that lowered + compiled a new executable
    cache_hits: int = 0  # calls served by a memoized executable
    dispatches: int = 0  # executable launches (per-op calls + program blocks)
    host_syncs: int = 0  # blocking host materialisations (host_value/cond)
    program_compiles: int = 0  # fused-program executables built
    program_dispatches: int = 0  # fused-program blocks launched
    retries: int = 0  # transient-fault dispatches re-attempted
    degraded_nodes: int = 0  # pallas nodes demoted to eager after a kernel fault
    escalations: int = 0  # hash targets regrown after overflow
    # host seconds in ``blaze.dispatch`` spans: each program dispatch up to
    # its enqueue (a new executable's first, compiling call excluded) and
    # each per-op dispatch attempt
    dispatch_s: float = 0.0
    feed_wait_s: float = 0.0  # host seconds run_stream waited for a block

    @property
    def hit_rate(self) -> float:
        return self.cache_hits / self.calls if self.calls else 0.0


# Default supervision policy: 3 attempts, 5 ms initial backoff, 30 s deadline.
# A module-level constant (not a fresh instance per session) so the default is
# introspectable and tests can compare against it.
_DEFAULT_RETRY = faults.RetryPolicy()


class BlazeSession:
    """Owns a mesh and a compiled-executable cache for Blaze MapReduce.

    >>> sess = BlazeSession()
    >>> for _ in range(10):
    ...     scores = sess.map_reduce(edges, contrib_mapper, "sum",
    ...                              jnp.zeros((n,), jnp.float32), env=scores)
    >>> sess.stats.compiles   # 1 — nine of the ten calls reused it
    """

    def __init__(
        self, mesh: Mesh | None = None, *,
        retry: faults.RetryPolicy | None = _DEFAULT_RETRY,
        escalate_overflow: bool = False, max_escalations: int = 3,
    ):
        self._mesh = mesh
        self._exec_cache: dict = {}
        self.stats = SessionStats()
        # Supervision: every dispatch the session issues (per-op, chunked
        # block, fused-program block, served batch) runs under ``retry`` —
        # transient faults are re-attempted with exponential backoff, kernel
        # faults demote the node's engine to eager, and (with
        # ``escalate_overflow=True``) hash overflow regrows the target along
        # the cost grid.  Escalation is opt-in because counted-and-dropped
        # overflow is itself a documented contract (see the differential
        # tests' near-capacity invariants).  ``retry=None`` disables
        # supervision (dispatch exceptions propagate raw, as before PR 9).
        self.retry = retry
        self.escalate_overflow = escalate_overflow
        self.max_escalations = max_escalations
        # resolved_hashes of nodes demoted to eager after a pallas kernel fault.
        # Consulted by every node build (per-op, program discovery, serve),
        # so a node degraded once stays degraded for the session — and its
        # eager executable caches under a *different* signature, leaving the
        # faulted pallas entry's cache slots untouched (no poisoning).
        self._degraded: set = set()
        # Session state (exec cache, stats, program carries) is not safe to
        # mutate from concurrent threads.  Multi-threaded front-ends — the
        # serving layer's dispatcher, notably — serialize all session work
        # under this lock; single-threaded drivers never need to take it.
        self.lock = threading.RLock()

    @property
    def mesh(self) -> Mesh:
        """The session's mesh (built lazily over all visible devices)."""
        if self._mesh is None:
            self._mesh = C.data_mesh()
        return self._mesh

    # -- the paper's API, session-scoped ------------------------------------

    def map_reduce(
        self,
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
        hierarchical: bool = True,
    ):
        """Run one MapReduce op, reusing this session's compiled executables.

        Same contract as the free ``repro.core.map_reduce``; ``mesh``
        overrides the session mesh for this call only (the override is part
        of the cache key, so mixed-mesh sessions stay correct).  ``engine``
        is one of ``"eager" | "pallas" | "naive" | "auto"``; ``"auto"`` (and
        the custom-reducer fallback for ``"pallas"``) resolves via
        ``resolve_engine`` *before* the cache key is built, so the resolved
        engine — reported in ``MapReduceStats.engine`` — is what keys the
        executable.  ``key_range`` (hash targets only) promises keys lie in
        ``[0, key_range)``: the shuffle then ships narrowed bucket keys and
        the pallas kernel sizes its combine table by the distinct-key bound.

        Since PR 5 this path wraps the call in a single-node logical plan
        (``repro.core.plan``): the resolve-engines pass runs on the node, the
        executable cache is keyed on the node's cache signature, and
        ``MapReduceStats.plan_hash`` carries the node's stable digest — equal
        to the hash the same op gets inside a fused program.

        On a multi-node ``("node", "data")`` mesh the
        ``hierarchical-collectives`` pass rewrites eligible dense reductions
        to the topology-aware two-hop plan (intra-node full precision,
        inter-node wire-compressed); ``hierarchical=False`` keeps the flat
        collective, the reference the hierarchical plan is tested against
        (``tests/test_multihost.py``).  A no-op on 1-D meshes either way.
        """
        red = get_reducer(reducer)
        mesh = mesh or self.mesh
        n_shards = C.shard_count(mesh)
        kind = _mr._source_kind(source)
        node = plan_mod.build_mapreduce_node(
            idx=0, kind=kind, src=plan_mod.source_desc(kind, source),
            source_key=None, mapper=mapper, red=red, target=target,
            engine=engine, wire=wire, key_range=key_range, env=env,
            degraded=self._degraded,
            n_nodes=C.n_nodes(mesh), hierarchical=hierarchical,
        )
        engine = node.engine

        if isinstance(source, C.ChunkedDistVector):
            return self._map_reduce_chunked(
                source, mapper, red, target, mesh, n_shards, engine, wire,
                env, key_range, node, return_stats,
            )
        if isinstance(target, C.DistHashMap):
            def dispatch_hash(tgt):
                return _mr._map_reduce_hash(
                    kind, source, mapper, red, tgt, mesh, n_shards,
                    node.engine, env, key_range=key_range,
                    cache=self._exec_cache, node=node,
                )

            out, stats = self._dispatch_supervised(
                lambda: dispatch_hash(target), node
            )
            out, stats = self._maybe_escalate(
                out, stats, target, red, node, dispatch_hash
            )
        else:
            out, stats = self._dispatch_supervised(
                lambda: _mr._map_reduce_dense(
                    kind, source, mapper, red, jnp.asarray(target), mesh,
                    n_shards, node.engine, wire, env, return_stats,
                    cache=self._exec_cache, node=node, hier=node.hier,
                ),
                node,
            )
        self.stats.calls += 1
        self.stats.compiles += stats.compiles
        self.stats.cache_hits += stats.cache_hits
        self.stats.dispatches += stats.dispatches
        return (out, stats) if return_stats else out

    def _map_reduce_chunked(
        self, source, mapper, red, target, mesh, n_shards, engine, wire,
        env, key_range, node, return_stats, prefetch=True,
    ):
        """Out-of-core standalone map_reduce: one dispatch per block.

        Streams the chunked source block-at-a-time through ONE memoized
        executable (the ``BlockView``'s traced ``base`` keeps the cache key
        fixed across blocks), merging each block's locally-reduced result
        into the running target — the paper's merged-into target semantics
        make block accumulation free.  Block k+1 is prefetched (disk read /
        decompress / host→device transfer on a background thread) while
        block k reduces.
        """
        import dataclasses as _dc

        from repro.data.pipeline import prefetch_iter

        hash_target = isinstance(target, C.DistHashMap)
        out = target if hash_target else jnp.asarray(target)
        emitted = shipped = payload = intra = inter = 0
        compiles = cache_hits = retries = 0
        last_stats = None

        def produce(b):
            return source.block_view(b, mesh)

        blocks = (
            prefetch_iter(produce, range(source.n_blocks), depth=2)
            if prefetch
            else ((b, produce(b)) for b in range(source.n_blocks))
        )
        for _b, bv in blocks:
            if hash_target:
                out, st = self._dispatch_supervised(
                    lambda bv=bv, out=out: _mr._map_reduce_hash(
                        "chunked", bv, mapper, red, out, mesh, n_shards,
                        node.engine, env, key_range=key_range,
                        cache=self._exec_cache, node=node,
                    ),
                    node,
                )
            else:
                out, st = self._dispatch_supervised(
                    lambda bv=bv, out=out: _mr._map_reduce_dense(
                        "chunked", bv, mapper, red, out, mesh, n_shards,
                        node.engine, wire, env, return_stats,
                        cache=self._exec_cache, node=node, hier=node.hier,
                    ),
                    node,
                )
            emitted = emitted + st.pairs_emitted
            shipped = shipped + st.pairs_shipped
            payload = payload + st.shuffle_payload_bytes
            intra = intra + st.intra_bytes
            inter = inter + st.inter_bytes
            compiles += st.compiles
            cache_hits += st.cache_hits
            retries += st.retries
            last_stats = st
        stats = _dc.replace(
            last_stats,
            pairs_emitted=emitted,
            pairs_shipped=shipped,
            shuffle_payload_bytes=payload,
            intra_bytes=intra,
            inter_bytes=inter,
            compiles=compiles,
            cache_hits=cache_hits,
            retries=retries,
            dispatches=source.n_blocks,
        )
        self.stats.calls += 1
        self.stats.compiles += stats.compiles
        self.stats.cache_hits += stats.cache_hits
        self.stats.dispatches += stats.dispatches
        return (out, stats) if return_stats else out

    # -- supervised dispatch (fault recovery) --------------------------------

    def supervised(self, attempt: Callable, *, program=None):
        """Run one dispatch ``attempt()`` under the session's retry policy.

        The recovery state machine (see docs/architecture.md):

        * ``faults.FatalFault`` — recorded and re-raised immediately;
        * an injected *kernel* fault (``kernel.*``) — if ``program`` is given
          and still has pallas nodes, those nodes are demoted to eager
          (``program.degrade()``) and the dispatch re-attempted.  Live carry
          is preserved: all fault points fire before the executable runs, so
          the retry replays the exact same block;
        * any other ``faults.TransientFault`` — re-attempted up to
          ``retry.attempts`` times with exponential backoff, bounded by
          ``retry.deadline_s``; exhaustion records the fault as fatal and
          re-raises;
        * a real (non-injected) exception propagates untouched: a kernel the
          compiler refuses is a fault to see, not a node to run elsewhere.

        Every injected fault is recorded in ``faults.registry`` under exactly
        one disposition, so the chaos suite's conservation law
        (injected == retried + degraded + escalated + fatal)
        holds across any schedule.
        """
        policy = self.retry
        if policy is None:
            return attempt()
        t0 = time.monotonic()
        delay = policy.backoff_s
        tries = 0
        while True:
            try:
                return attempt()
            except faults.FatalFault as e:
                faults.record("fatal", e)
                raise
            except faults.TransientFault as e:
                if e.point.startswith("kernel.") and program is not None:
                    if program.degrade() > 0:
                        faults.record("degraded", e)
                        self.stats.degraded_nodes += 1
                        continue
                tries += 1
                deadline_hit = (
                    policy.deadline_s is not None
                    and time.monotonic() - t0 + delay > policy.deadline_s
                )
                if tries >= policy.attempts or deadline_hit:
                    faults.record("fatal", e)
                    raise
                faults.record("retried", e)
                self.stats.retries += 1
                if delay > 0:
                    with tracing.span("retry"):
                        time.sleep(delay)
                delay *= policy.multiplier

    def _degrade_op_node(self, node, e) -> None:
        """Demote a per-op node to eager after an injected kernel fault.

        Its ``resolved_hash`` lands in ``self._degraded`` so every later
        build of the same logical node (per-op, program, serve) is born degraded; the
        faulted pallas executable's cache entry is dropped, and the eager
        rebuild caches under the node's *new* signature (engine is part of
        ``stable_desc``), so the pallas entry can never be served again —
        and nothing else in the cache is touched.
        """
        self._degraded.add(node.resolved_hash)
        if node.cache_sig is not None:
            self._exec_cache.pop(node.cache_sig, None)
        plan_mod.degrade_node(node)
        faults.record("degraded", e)
        self.stats.degraded_nodes += 1

    def _dispatch_supervised(self, dispatch: Callable, node):
        """``supervised`` specialised to one per-op node: kernel faults
        degrade just this node (not a whole program) and the returned
        ``MapReduceStats`` carries the recovery provenance
        (``degraded_engine``, ``retries``)."""

        def attempt():
            with tracing.span("dispatch", self.stats, "dispatch_s"):
                return dispatch()

        policy = self.retry
        if policy is None:
            return attempt()
        t0 = time.monotonic()
        delay = policy.backoff_s
        tries = retries = 0
        while True:
            try:
                out, stats = attempt()
                if retries or node.degraded_from is not None:
                    stats = dataclasses.replace(
                        stats, retries=retries,
                        degraded_engine=node.degraded_from,
                    )
                return out, stats
            except faults.FatalFault as e:
                faults.record("fatal", e)
                raise
            except faults.TransientFault as e:
                if e.point.startswith("kernel.") and node.engine == "pallas":
                    self._degrade_op_node(node, e)
                    continue
                tries += 1
                deadline_hit = (
                    policy.deadline_s is not None
                    and time.monotonic() - t0 + delay > policy.deadline_s
                )
                if tries >= policy.attempts or deadline_hit:
                    faults.record("fatal", e)
                    raise
                faults.record("retried", e)
                self.stats.retries += 1
                retries += 1
                if delay > 0:
                    with tracing.span("retry"):
                        time.sleep(delay)
                delay *= policy.multiplier

    def _maybe_escalate(self, out, stats, target, red, node, dispatch):
        """Hash-overflow recovery: if the dispatch dropped pairs (overflow
        grew), regrow the target to the next capacity on the cost grid and
        re-dispatch the *same* op against the grown original.

        ``map_reduce`` is functional (merged-into-target returns a NEW
        container) and ``shard_of_key`` is capacity-independent, so the
        re-dispatch is exact — the failed output is simply discarded.
        Bounded by ``max_escalations``; each round is counted in
        ``MapReduceStats.escalations`` and ``session.stats.escalations``.
        """
        if self.retry is None or not self.escalate_overflow:
            return out, stats
        base = target.total_overflow()
        new = out.total_overflow()
        escal = 0
        cur = target
        while new > base and escal < self.max_escalations:
            cap = cost_mod.next_capacity(cur.capacity_per_shard)
            if cap is None:
                break
            cur = self._grow_hash_target(cur, cap, red)
            escal += 1
            out, st = self._dispatch_supervised(
                lambda tgt=cur: dispatch(tgt), node
            )
            stats = dataclasses.replace(
                st,
                escalations=escal,
                compiles=stats.compiles + st.compiles,
                cache_hits=stats.cache_hits + st.cache_hits,
                dispatches=stats.dispatches + st.dispatches,
                retries=stats.retries + st.retries,
            )
            base = cur.total_overflow()
            new = out.total_overflow()
        if escal:
            self.stats.escalations += escal
        return out, stats

    def _grow_hash_target(self, target: C.DistHashMap, new_cap: int, red):
        """Rebuild ``target`` with ``new_cap`` slots per shard, re-inserting
        every live entry on its original shard (``shard_of_key`` does not
        depend on capacity, so entries never migrate between shards).
        Historical per-shard overflow counters are carried over so the
        caller's overflow-delta test sees only *new* drops."""
        keys = np.asarray(jax.device_get(target.table.keys))
        vals = np.asarray(jax.device_get(target.table.vals))
        ovf = np.asarray(jax.device_get(target.table.overflow))
        val_shape = vals.shape[2:]
        grown = C.make_dist_hashmap(
            self.mesh, new_cap, val_shape=val_shape,
            val_dtype=target.table.vals.dtype, reducer=red.name,
        )
        nk = np.array(jax.device_get(grown.table.keys))
        nv = np.array(jax.device_get(grown.table.vals))
        no = np.array(jax.device_get(grown.table.overflow))
        for s in range(target.n_shards):
            valid = keys[s] != C.EMPTY_KEY
            if not valid.any():
                no[s] = no[s] + ovf[s]
                continue
            t = C.hashmap_insert(
                C.HashTable(
                    jnp.asarray(nk[s]), jnp.asarray(nv[s]),
                    jnp.asarray(no[s]),
                ),
                jnp.asarray(keys[s]), jnp.asarray(vals[s]),
                jnp.asarray(valid), red, max_probes=64,
            )
            nk[s] = np.asarray(jax.device_get(t.keys))
            nv[s] = np.asarray(jax.device_get(t.vals))
            no[s] = np.asarray(jax.device_get(t.overflow)) + ovf[s]
        table = C.HashTable(
            jax.device_put(jnp.asarray(nk), grown.table.keys.sharding),
            jax.device_put(jnp.asarray(nv), grown.table.vals.sharding),
            jax.device_put(jnp.asarray(no), grown.table.overflow.sharding),
        )
        return dataclasses.replace(grown, table=table)

    # -- fused iteration programs (see repro.core.program) -------------------

    def program(self, step_fn: Callable, *, mesh=None, passes=None,
                hierarchical: bool = True):
        """Lower ``step_fn(ctx, state) -> state`` — a whole iteration of
        MapReduce ops plus elementwise glue — into ONE optimized executable.

        ``ctx`` mirrors the session API in-trace (``ctx.map_reduce``,
        ``ctx.foreach``, ``ctx.topk``); iteration-varying values go through
        ``state`` (a pytree that must keep its structure/shapes across
        steps).  Discovery builds an explicit logical plan
        (``repro.core.plan``) and runs the optimizer passes on it — per-node
        engine resolution, collective batching, CSE, dead-source pruning;
        ``passes=()`` disables the optional three for A/B comparisons, and
        ``hierarchical=False`` keeps collectives flat on a multi-node mesh
        (the reference the hierarchical plan is tested against).  Run the result with
        ``program(state, n_iters)`` or ``run_loop``; render the plan with
        ``session.explain(program)``.
        """
        from repro.core.program import Program

        return Program(
            self, step_fn, mesh=mesh or self.mesh, passes=passes,
            hierarchical=hierarchical,
        )

    def explain(self, program, state=None) -> str:
        """Render ``program``'s optimized logical plan, Spark-EXPLAIN-style:
        nodes with resolved engines and wire dtypes, the source table,
        batched collective groups, CSE/prune effects and the plan hash.

        The plan is built lazily per state signature; pass ``state`` to
        build it without dispatching (cheap — compilation stays lazy under
        jit), or call after the program has run at least once.
        """
        plan = program.build(state) if state is not None else program.plan
        if plan is None:
            raise ValueError(
                "program has no plan yet — pass state= (or dispatch it once)"
            )
        return plan.render()

    def run_loop(
        self,
        program,
        state,
        *,
        cond: Callable | None = None,
        max_iters: int,
        unroll: int = 1,
        checkpoint=None,
        checkpoint_every: int | None = None,
        resume: bool = False,
    ):
        """Drive a fused ``Program``: ``unroll`` iterations per dispatch.

        Each dispatch runs a device-resident ``fori_loop`` block; the
        convergence test ``cond(state) -> bool`` (truthy = converged, stop)
        is evaluated on the host only *between* blocks — one host sync per
        ``unroll`` iterations instead of one per iteration.  Returns
        ``(state, LoopInfo)``; ``LoopInfo`` carries the assertable counters
        (iterations, dispatches, host_syncs, compiles).

        ``checkpoint=`` (a ``CheckpointManager`` or directory path) with
        ``checkpoint_every=k`` saves program state + carry + position every
        k iterations at dispatch boundaries; ``resume=True`` restores the
        latest checkpoint first and continues from its iteration — the
        resumed run is bit-equal to the uninterrupted one
        (``LoopInfo.resumed_from`` carries the restored position).
        Dispatches run supervised (see ``supervised``).
        """
        from repro.core.program import LoopInfo, _as_checkpoint_manager

        if unroll < 1:
            raise ValueError(f"unroll must be >= 1, got {unroll}")
        manager = _as_checkpoint_manager(checkpoint)
        if resume and manager is None:
            raise ValueError("resume=True requires checkpoint=")
        compiles0 = program.stats.compiles
        it = dispatches = host_syncs = 0
        resumed_from = None
        if resume:
            state, pos = program.restore_checkpoint(manager, state)
            if pos is not None:
                resumed_from = it = pos
        start_it = it
        last_saved = it
        converged = False
        while it < max_iters:
            u = min(unroll, max_iters - it)
            state = self.supervised(
                lambda state=state, u=u: program(state, u), program=program
            )
            dispatches += 1
            it += u
            if manager is not None and checkpoint_every:
                if it - last_saved >= checkpoint_every:
                    program.save_checkpoint(manager, state, it)
                    last_saved = it
            if cond is not None:
                self.stats.host_syncs += 1
                host_syncs += 1
                with tracing.span("sync"):
                    done = bool(cond(state))
                if done:
                    converged = True
                    break
        return state, LoopInfo(
            iterations=it - start_it,
            dispatches=dispatches,
            host_syncs=host_syncs,
            converged=converged,
            compiles=program.stats.compiles - compiles0,
            resumed_from=resumed_from,
        )

    def run_stream(
        self,
        program,
        state,
        *,
        cond: Callable | None = None,
        max_epochs: int = 1,
        prefetch: bool = True,
        depth: int = 2,
        checkpoint=None,
        checkpoint_every: int | None = None,
        resume: bool = False,
    ):
        """Drive a fused ``Program`` over its chunked (out-of-core) sources.

        The ``run_loop`` analogue one level down the memory hierarchy: each
        *epoch* streams every host-resident block through the program's ONE
        executable (block k+1 prefetched while block k reduces), and
        ``cond(state)`` is evaluated once per epoch.  Returns
        ``(state, StreamInfo)``.

        ``checkpoint=`` / ``checkpoint_every=`` / ``resume=`` mirror
        ``run_loop`` at epoch granularity: the stream position saved is the
        epoch count, and a resumed run replays the remaining epochs
        bit-equal to the uninterrupted one (``StreamInfo.resumed_from``).
        """
        return program.run_stream(
            state, max_epochs=max_epochs, cond=cond, prefetch=prefetch,
            depth=depth, checkpoint=checkpoint,
            checkpoint_every=checkpoint_every, resume=resume,
        )

    def host_value(self, x):
        """Materialise ``x`` on the host (the driver's explicit sync point),
        counting it in ``stats.host_syncs`` so per-op loops and fused
        ``run_loop`` blocks are comparable."""
        self.stats.host_syncs += 1
        with tracing.span("sync"):
            return jax.device_get(x)

    def foreach(self, v: C.DistVector, fn: Callable, env: Any = None) -> C.DistVector:
        """Session-scoped ``foreach`` (same executable-reuse contract via
        ``env``; the elementwise cache is shared process-wide)."""
        return C.foreach(v, fn, env=env)

    def topk(
        self, v: C.DistVector, k: int, score_fn: Callable | None = None,
        env: Any = None, mesh: Mesh | None = None,
    ):
        """Session-scoped ``topk``: selects on-device, then materialises the
        ``k·n_shards`` candidates on the host — a blocking sync, counted in
        ``stats.host_syncs`` (drivers that bypassed this used to undercount;
        see ``knn``)."""
        self.stats.host_syncs += 1
        return C.topk(v, k, score_fn=score_fn, mesh=mesh or self.mesh, env=env)

    def distribute(self, x, mesh: Mesh | None = None) -> C.DistVector:
        """``distribute`` onto this session's mesh."""
        return C.distribute(x, mesh or self.mesh)

    def chunked(
        self, x, block_rows: int, mesh: Mesh | None = None, **kwargs
    ) -> C.ChunkedDistVector:
        """``distribute`` for datasets that don't fit on device: host array →
        out-of-core blocks on this session's mesh (``compress=`` /
        ``spill_dir=`` / ``max_resident=`` control the byte provider)."""
        return C.chunked(x, block_rows, mesh or self.mesh, **kwargs)

    # -- observability -------------------------------------------------------

    def cache_info(self) -> dict:
        """Executable-cache snapshot: entries + cumulative counters."""
        return {
            "entries": len(self._exec_cache),
            "calls": self.stats.calls,
            "compiles": self.stats.compiles,
            "cache_hits": self.stats.cache_hits,
            "hit_rate": self.stats.hit_rate,
            "dispatches": self.stats.dispatches,
            "host_syncs": self.stats.host_syncs,
            "program_compiles": self.stats.program_compiles,
            "program_dispatches": self.stats.program_dispatches,
            "retries": self.stats.retries,
            "degraded_nodes": self.stats.degraded_nodes,
            "escalations": self.stats.escalations,
            "dispatch_s": self.stats.dispatch_s,
            "feed_wait_s": self.stats.feed_wait_s,
        }

    def clear_cache(self) -> None:
        """Drop all memoized executables (counters keep accumulating)."""
        self._exec_cache.clear()


# -- process-wide default session --------------------------------------------

_default_lock = threading.Lock()
_default_session: BlazeSession | None = None


def get_default_session() -> BlazeSession:
    """The lazily created session backing the free ``map_reduce``."""
    global _default_session
    if _default_session is None:
        with _default_lock:
            if _default_session is None:
                _default_session = BlazeSession()
    return _default_session


def set_default_session(session: BlazeSession) -> BlazeSession | None:
    """Install ``session`` as the process default; returns the previous one."""
    global _default_session
    with _default_lock:
        prev, _default_session = _default_session, session
    return prev


def reset_default_session() -> None:
    """Forget the default session (a fresh one is built on next use)."""
    global _default_session
    with _default_lock:
        _default_session = None


def resolve(
    session: BlazeSession | None, mesh: Mesh | None
) -> tuple[BlazeSession, Mesh]:
    """(session or default, mesh or session's) — the driver entry idiom."""
    sess = session if session is not None else get_default_session()
    return sess, (mesh or sess.mesh)
