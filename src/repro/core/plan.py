"""The Blaze logical-plan IR: explicit plans, optimizer passes, EXPLAIN.

The paper's pitch is that ONE MapReduce function plus three utilities beats
Spark's ~30 primitives — but Spark keeps one decisive advantage:
*introspection*.  A Spark job is a logical plan you can optimize and
``EXPLAIN``; a Blaze job is a C++ call tree you can only run.  Until PR 5 this
reproduction had the same blind spot: the program layer traced a whole
iteration and then consumed the discovered structure *inline* — engine
choice, wire narrowing and op ordering were decided ad hoc per op, and no
optimization could look across ops.

This module is the missing plan:

* ``Plan`` — a DAG of :class:`MapReduceNode` / :class:`ForeachNode` /
  :class:`ContainerOpNode` / :class:`GlueNode` nodes in call order, plus the
  source table, residual/hash-state edges, batch groups and pass log.
  ``repro.core.program`` *builds* one during discovery instead of consuming
  the trace; both executors consume it — standalone ``map_reduce`` wraps a
  single-node plan (``single_op_plan``), ``Program`` lowers the full DAG.
* **Passes** — the optimizations an explicit plan makes possible:

  - ``resolve-engines``  (:func:`resolve_engine`, moved here from
    ``session.py``): engines are chosen *per node*, so one program can mix
    pallas-dense, pallas-hash and eager ops;
  - ``batch-collectives``: independent dense reductions with the same
    (reducer, wire, dtype) in one iteration are concatenated into ONE fused
    collective — GMM's EM round used to issue 4 separate psums, now 2
    (asserted via the new ``collectives_per_iter`` stat).  This is the BSP
    "batch the whole superstep" fix (Pace, arXiv:1203.2081) for the
    dispatch/collective overhead Li (arXiv:1811.04875) identifies;
  - ``cse``: two ops with identical (source, mapper, reducer, target,
    engine, wire, env) run once — the second reuses the first's result;
  - ``prune-dead-sources``: ops whose results are provably unused are
    dropped, and sources referenced only by dropped ops are never shipped
    into the executable.

* ``Plan.render()`` — the Spark-``EXPLAIN`` analogue: nodes, resolved
  engines, wire dtypes, batched collective groups, pass effects.  Golden
  snapshots for all six paper algorithms live in ``tests/goldens/`` and are
  diffed in CI (``tools/check_explain_goldens.py``).
* **Plan hashes as cache keys** — every node carries a stable digest
  (``node.hash``) and an identity-faithful cache signature
  (``node.cache_sig``); the session's executable cache is keyed on the
  latter, and the per-op and program paths provably agree because both
  derive their keys from the same node builder (asserted in
  ``tests/test_plan.py``).
"""
from __future__ import annotations

import dataclasses
import hashlib
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import containers as C
from repro.core import cost as cost_mod
from repro.core.cost import PALLAS_AUTO_MAX_KEYS
from repro.core.reducers import Reducer

__all__ = [
    "DEFAULT_PASSES",
    "ENGINES",
    "PALLAS_AUTO_MAX_KEYS",
    "ContainerOpNode",
    "ForeachNode",
    "GlueNode",
    "MapReduceNode",
    "Plan",
    "SourceInfo",
    "abstract_sig",
    "apply_hierarchical",
    "build_mapreduce_node",
    "hier_collective_desc",
    "node_key_count",
    "resolve_engine",
    "single_op_plan",
]

ENGINES = ("eager", "pallas", "naive", "auto")

# The optimizer passes a Program runs by default, in order.  resolve-engines
# is not optional (a node without a resolved engine cannot lower); the other
# three can be switched off per program (``session.program(..., passes=())``)
# — the unoptimized reference the tests compare optimized plans against.
DEFAULT_PASSES = ("cse", "batch-collectives", "prune-dead-sources")

# PALLAS_AUTO_MAX_KEYS now lives in repro.core.cost as the fallback cost
# model's calibration anchor (re-exported here for back-compat): the modelled
# eager-vs-pallas crossover sits at exactly K == 4096 keys, so engine="auto"
# keeps the policy PR 2's differential matrix pinned.


def node_key_count(target) -> int:
    """Accumulator rows ``k`` the cost model prices a node by: the dense key
    range, or the hash table's per-shard capacity.  0 when unknowable."""
    if isinstance(target, C.DistHashMap):
        return target.capacity_per_shard
    return jnp.asarray(target).shape[0] if jnp.ndim(target) else 0


def resolve_engine(engine: str, target, reducer: Reducer) -> str:
    """The per-node engine-resolution pass (``engine="auto"`` policy plus
    reducer-compatibility fallbacks).

    Every target kind has a kernel: dense targets run the segment-reduce
    kernel (``Reducer.pallas_segment``), ``DistHashMap`` targets the
    hash-aggregation kernel (``Reducer.pallas_hash``).  Only a *custom*
    reducer — which carries neither — falls back to the eager plan
    (``engine="pallas"`` degrades rather than erroring, so drivers can pass
    one engine for mixed pipelines, and the resolved name in
    ``MapReduceStats.engine`` / ``MapReduceNode.engine`` matches the plan
    that runs).

    ``"auto"`` asks the calibrated fallback cost model
    (``cost.pick_engine``): the modelled-cheaper engine over ``k``
    accumulator rows (dense key range / hash ``capacity_per_shard``), whose
    calibration puts the eager/pallas crossover at exactly
    ``k == PALLAS_AUTO_MAX_KEYS`` — deterministic, and pinned against the
    old static rule by the PR 2 differential matrix.  Lives here (not in
    ``session.py``) since PR 5: resolution is a planning pass applied
    node-by-node, which is what lets one fused program mix engines.
    """
    if engine not in ENGINES:
        raise ValueError(f"unknown engine {engine!r}; choose from {ENGINES}")
    hash_target = isinstance(target, C.DistHashMap)
    kernel = reducer.pallas_hash if hash_target else reducer.pallas_segment
    if engine == "pallas" and kernel is None:
        return "eager"
    if engine != "auto":
        return engine
    if kernel is None:
        return "eager"
    return cost_mod.pick_engine(node_key_count(target))


def abstract_sig(tree) -> tuple:
    """Hashable (treedef, shapes/dtypes) signature — cheap cache key.

    (Moved from ``repro.core.mapreduce`` so the plan layer sits below the
    engine; ``mapreduce._abstract`` re-exports it.)
    """
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return treedef, tuple(
        (getattr(x, "shape", ()), str(getattr(x, "dtype", type(x))))
        for x in leaves
    )


def _dtype_name(dt) -> str:
    return str(jnp.dtype(dt))


def _fn_name(fn: Callable) -> str:
    mod = getattr(fn, "__module__", "?")
    qual = getattr(fn, "__qualname__", getattr(fn, "__name__", repr(fn)))
    return f"{mod}.{qual}"


def _sig_desc(sig: tuple) -> str:
    """Render an ``abstract_sig`` compactly and deterministically."""
    _, leaves = sig
    if not leaves:
        return "-"
    return ",".join(f"{dt}[{'x'.join(map(str, sh))}]" for sh, dt in leaves)


def source_desc(kind: str, source) -> str:
    """Stable human-readable description of a plan source."""
    if kind == "range":
        return f"range[{source.start}:{source.stop}:{source.step}]"
    if kind == "vector":
        d = source.data
        return (
            f"vector {_dtype_name(d.dtype)}[{'x'.join(map(str, d.shape))}]"
            f" n={source.n}"
        )
    if kind == "chunked":
        tail = "x".join(map(str, getattr(source, "shape_tail", ())))
        shape = f"{source.block_rows}{'x' + tail if tail else ''}"
        return (
            f"chunked {_dtype_name(source.dtype)}[{shape}]"
            f" n={source.n} blocks={source.n_blocks}"
        )
    t = source.table
    return (
        f"hashmap cap={t.keys.shape[-1]} "
        f"{_dtype_name(t.vals.dtype)}[{'x'.join(map(str, t.vals.shape[2:]))}]"
    )


@dataclasses.dataclass
class SourceInfo:
    """One entry of the plan's source table (what the executable ships)."""

    key: tuple  # identity key (repro.core.program._source_key)
    desc: str  # stable rendering for explain/hash
    source: Any  # the container object (operands are derived from it)
    pruned: bool = False  # no live node references it -> not shipped


@dataclasses.dataclass
class MapReduceNode:
    """One MapReduce op: sources, reducer, target, wire — and what the
    passes decided for it (engine, batch group, CSE, deadness)."""

    idx: int  # call-order index within the plan
    kind: str  # source kind: range | vector | hashmap (incl. program-locals)
    src: str  # stable source description ("local[i]" for program locals)
    source_key: tuple | None  # source-table key (None for program locals)
    mapper: Callable
    reducer: str
    target_kind: str  # "dense" | "hash"
    target_desc: str  # e.g. "dense float32[4x3]" / "hash cap=256 int32"
    engine_requested: str
    engine: str  # after the resolve-engines pass
    wire: str
    key_range: int | None = None
    env_sig: tuple = ()
    feedback: bool = False  # int8 error-feedback sum (never batched/CSE'd)
    residual_spec: tuple | None = None  # (shape, dtype) when feedback
    # -- pass annotations ----------------------------------------------------
    group: int | None = None  # batched-collective group id (size > 1 only)
    cse_of: int | None = None  # idx of the identical earlier node it reuses
    dead: bool = False  # result provably unused -> op pruned
    collective: str = ""  # what carries this op's shuffle
    cache_sig: tuple | None = None  # identity-faithful executable cache key
    cost_estimate: float | None = None  # model units for the resolved engine
    # -- fault supervision: ``resolved_hash`` is the node's hash as built,
    # before any degradation rewrites ``engine`` (and so ``hash``); it keys
    # the session's degraded set.  ``degraded_from`` is the engine a kernel
    # fault degraded this node FROM (None = never degraded).
    resolved_hash: str = ""
    degraded_from: str | None = None
    # -- hierarchical-collectives pass: True when the node's collective was
    # rewritten to the two-hop (intra-node full precision, inter-node wire)
    # topology.  Rendered into stable_desc ONLY when set, so 1-D plans hash
    # and render exactly as before the pass existed.
    hier: bool = False

    def stable_desc(self) -> str:
        desc = (
            f"map_reduce {self.reducer} fn={_fn_name(self.mapper)} "
            f"src={self.kind}:{self.src} "
            f"-> {self.target_desc} engine={self.engine} wire={self.wire} "
            f"key_range={self.key_range} env={_sig_desc(self.env_sig)}"
        )
        if self.hier:
            desc += " hier"
        return desc

    @property
    def hash(self) -> str:
        """Stable digest of everything that shapes this op's plan — equal for
        the per-op and program spellings of the same op (tested)."""
        return hashlib.sha1(self.stable_desc().encode()).hexdigest()[:12]


@dataclasses.dataclass
class ForeachNode:
    """Elementwise map over a vector source; output stays shard-local."""

    idx: int
    src: str
    source_key: tuple | None
    fn: Callable

    def stable_desc(self) -> str:
        return f"foreach src={self.src} fn={_fn_name(self.fn)}"


@dataclasses.dataclass
class ContainerOpNode:
    """A container-level plan node (``topk``): the op's plan is fixed by the
    container, so an ``engine=`` request cannot change it — the node records
    the request and surfaces that it was ignored instead of dropping it."""

    idx: int
    op: str  # "topk"
    src: str
    source_key: tuple | None
    params: str  # e.g. "k=100 score=_neg_sq_dist"
    engine_requested: str | None = None  # surfaced, never applied

    def stable_desc(self) -> str:
        return f"{self.op} src={self.src} {self.params}"


@dataclasses.dataclass
class GlueNode:
    """The user's interstitial jnp glue (opaque; stays in the step fn)."""

    idx: int
    desc: str

    def stable_desc(self) -> str:
        return f"glue {self.desc}"


@dataclasses.dataclass
class Plan:
    """An optimized logical plan: what ``session.explain`` renders and what
    both executors lower."""

    nodes: list
    sources: list[SourceInfo]
    state_desc: str
    n_shards: int
    passes: tuple[str, ...]
    n_nodes: int = 1  # simulated/real host rows of the mesh (1 = 1-D mesh)
    groups: dict[int, list[int]] = dataclasses.field(default_factory=dict)
    group_keys: dict[int, tuple] = dataclasses.field(default_factory=dict)
    collectives_per_iter: int = 0  # after batching/CSE/pruning
    collectives_unbatched: int = 0  # the same plan, one collective per op
    cse_hits: int = 0
    dead_ops: int = 0
    pruned_sources: int = 0
    residual_specs: list[tuple] = dataclasses.field(default_factory=list)
    hash_targets: dict = dataclasses.field(default_factory=dict)

    @property
    def hash(self) -> str:
        """Stable digest of the whole optimized plan (nodes + sources +
        state + groups) — the program-level cache identity."""
        parts = [self.state_desc, f"shards={self.n_shards}"]
        if self.n_nodes > 1:  # absent on 1-D meshes: legacy hashes unchanged
            parts.append(f"nodes={self.n_nodes}")
        parts += [n.stable_desc() for n in self.nodes]
        parts += [s.desc for s in self.sources if not s.pruned]
        parts += [f"group{g}={idxs}" for g, idxs in sorted(self.groups.items())]
        return hashlib.sha1("\n".join(parts).encode()).hexdigest()[:12]

    def live_sources(self) -> list[SourceInfo]:
        return [s for s in self.sources if not s.pruned]

    def mapreduce_nodes(self) -> list[MapReduceNode]:
        return [n for n in self.nodes if isinstance(n, MapReduceNode)]

    # -- EXPLAIN -------------------------------------------------------------

    def render(self, title: str = "Blaze logical plan") -> str:
        lines = [f"== {title} (hash {self.hash}) =="]
        if self.n_nodes > 1:
            per = self.n_shards // self.n_nodes
            lines.append(f"mesh: node[{self.n_nodes}]×data[{per}]")
        else:
            lines.append(f"mesh: data[{self.n_shards}]")
        lines.append(f"state: {self.state_desc}")
        lines.append(
            "passes: resolve-engines"
            + (", hierarchical-collectives" if self.n_nodes > 1 else "")
            + ("".join(f", {p}" for p in self.passes))
        )
        lines.append("nodes:")
        for n in self.nodes:
            flags = []
            if isinstance(n, MapReduceNode):
                if n.dead:
                    flags.append("DEAD (pruned)")
                if n.cse_of is not None:
                    flags.append(f"CSE -> node [{n.cse_of}]")
                if n.group is not None:
                    flags.append(f"group {chr(ord('A') + n.group)}")
                if n.feedback:
                    flags.append("int8 feedback")
                if n.degraded_from is not None:
                    flags.append(
                        f"degraded {n.degraded_from!r} -> {n.engine!r} "
                        "(kernel fault)"
                    )
                elif n.engine_requested != n.engine:
                    flags.append(f"requested {n.engine_requested!r}")
                mapper_name = _fn_name(n.mapper).rsplit(".", 1)[-1]
                body = (
                    f"map_reduce {n.reducer:<4} fn={mapper_name} "
                    f"src={n.kind}:{n.src} -> "
                    f"{n.target_desc}  engine={n.engine} wire={n.wire}"
                )
                if n.cost_estimate is not None:
                    body += f" cost~{int(n.cost_estimate)}"
                if n.key_range is not None:
                    body += f" key_range={n.key_range}"
                if n.collective and not n.dead and n.cse_of is None:
                    body += f"  via {n.collective}"
            elif isinstance(n, ForeachNode):
                body = f"foreach    src={n.src}  fn={_fn_name(n.fn).rsplit('.', 1)[-1]}"
            elif isinstance(n, ContainerOpNode):
                body = f"{n.op:<10} src={n.src}  {n.params}"
                if n.engine_requested and n.engine_requested != "auto":
                    flags.append(
                        f"engine={n.engine_requested!r} ignored "
                        "(container-level plan)"
                    )
            else:
                body = f"glue       {n.desc}"
            suffix = f"   [{'; '.join(flags)}]" if flags else ""
            lines.append(f"  [{n.idx}] {body}{suffix}")
        if self.sources:
            lines.append("sources:")
            for s in self.sources:
                mark = "  (pruned: no live consumer)" if s.pruned else ""
                lines.append(f"  - {s.desc}{mark}")
        stream = [
            s for s in self.sources
            if not s.pruned and s.desc.startswith("chunked ")
        ]
        if stream:
            lines.append("stream schedule (out-of-core, one executable):")
            for s in stream:
                blocks = getattr(s.source, "n_blocks", "?")
                rows = getattr(s.source, "block_rows", "?")
                lines.append(
                    f"  - {s.desc}: {blocks} block dispatches of {rows} rows"
                    " each; block k+1 prefetched (host thread) while block k"
                    " reduces on device"
                )
        if self.groups:
            lines.append("batched collective groups:")
            for g, idxs in sorted(self.groups.items()):
                # Key is (red, wire, dtype) plus, on multi-node meshes, the
                # hier flag — groups never mix hierarchical and flat reduces.
                key = self.group_keys.get(g, ("?", "?", "?"))
                red, wire, dt = key[:3]
                hier = len(key) > 3 and key[3]
                lines.append(
                    f"  {chr(ord('A') + g)}: {red}/{wire}/{dt}"
                    + ("/hier" if hier else "")
                    + f" carries nodes {idxs} ({len(idxs)} collectives -> 1)"
                )
        lines.append(
            f"collectives/iter: {self.collectives_per_iter} "
            f"(unbatched: {self.collectives_unbatched})"
            + (f"; cse hits: {self.cse_hits}" if self.cse_hits else "")
            + (f"; dead ops pruned: {self.dead_ops}" if self.dead_ops else "")
            + (
                f"; sources pruned: {self.pruned_sources}"
                if self.pruned_sources
                else ""
            )
        )
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# Node builders (shared by the per-op and program paths)
# ---------------------------------------------------------------------------


def target_desc_of(target) -> tuple[str, str]:
    """(target_kind, stable description) for a dense array or DistHashMap."""
    if isinstance(target, C.DistHashMap):
        t = target.table
        return "hash", (
            f"hash cap={t.keys.shape[-1]} {_dtype_name(t.vals.dtype)}"
        )
    t = jnp.asarray(target)
    return "dense", f"dense {_dtype_name(t.dtype)}[{'x'.join(map(str, t.shape))}]"


def hier_collective_desc(reducer_name: str, wire: str) -> str:
    """EXPLAIN rendering of a hierarchical collective, e.g.
    ``psum[node×data, hier, wire=int8@inter]``: the intra-node hop always
    runs at full precision; ``@inter`` marks where wire narrowing applies."""
    op = "psum" if reducer_name == "sum" else f"{reducer_name}-reduce"
    desc = f"{op}[node×data, hier"
    if wire != "none" and reducer_name == "sum":
        desc += f", wire={wire}@inter"
    return desc + "]"


def apply_hierarchical(node: MapReduceNode, n_nodes: int) -> bool:
    """The ``hierarchical-collectives`` pass, applied per node.

    Rewrites an eligible node's collective to the two-hop topology: a
    full-precision intra-node reduce over the fast links first, then the
    inter-node reduce over node-level partials — with wire narrowing (when
    requested) applied only to the slow inter-node hop.  Eligible nodes are
    dense reductions on the eager/pallas plans (``naive`` all-gathers raw
    pairs and hash targets shuffle point-to-point — neither has a reduction
    tree to reshape).  A no-op on 1-D meshes (``n_nodes <= 1``), so every
    pre-existing plan hash and explain golden is unchanged.  Composes with
    ``batch-collectives``: batched groups carry the member nodes' shared
    ``hier`` flag through one concatenated two-hop reduce.
    """
    if (
        n_nodes <= 1
        or node.target_kind != "dense"
        or node.engine not in ("eager", "pallas")
    ):
        return False
    node.hier = True
    node.collective = hier_collective_desc(node.reducer, node.wire)
    return True


def degrade_node(node: MapReduceNode) -> None:
    """Degrade a kernel-faulted node to the always-available eager engine.

    Records where the node came FROM (rendered by ``explain`` and surfaced
    as ``MapReduceStats.degraded_engine``).  The rewritten ``engine`` moves
    ``node.hash``/``cache_sig`` so the degraded executable caches beside,
    never over, the faulted one; ``resolved_hash`` was captured before and
    stays put.
    """
    if node.engine == "eager":
        return
    node.degraded_from = node.engine
    node.engine = "eager"


def build_mapreduce_node(
    idx: int,
    kind: str,
    src: str,
    source_key: tuple | None,
    mapper: Callable,
    red: Reducer,
    target,
    engine: str,
    wire: str,
    key_range: int | None,
    env: Any,
    degraded: set | None = None,
    n_nodes: int = 1,
    hierarchical: bool = True,
) -> MapReduceNode:
    """Build a MapReduce node and run the resolve-engines pass on it.

    This is THE node constructor: ``BlazeSession.map_reduce`` builds its
    single-node plan through it and ``ProgramContext`` builds every program
    node through it, which is why the two paths produce identical node
    hashes for the same op.  A node whose ``resolved_hash`` is in
    ``degraded`` (the session's kernel-faulted nodes) is degraded to eager
    before it is returned.

    On multi-node meshes (``n_nodes > 1``) the ``hierarchical-collectives``
    pass runs here too — per node, like resolve-engines — unless the caller
    opts out (``hierarchical=False``, the flat-topology reference).  It
    runs BEFORE ``resolved_hash`` is captured: a hierarchical node is a
    different plan from its flat twin.
    """
    target_kind, tdesc = target_desc_of(target)
    if target_kind == "hash":
        wire = "none"  # wire narrowing is a dense-target concept
    resolved = resolve_engine(engine, target, red)
    if target_kind == "dense":
        t = jnp.asarray(target)
        n_elems = int(np.prod(t.shape)) if t.shape else 1
        vb = {"bf16": 2, "int8": 1}.get(wire, jnp.dtype(t.dtype).itemsize)
        if resolved == "naive":
            collective = "all_gather[raw pairs]"
        else:
            collective = f"psum[{n_elems}x{vb}B]" if red.name == "sum" else (
                f"{red.name}-reduce[{n_elems}]"
            )
    else:
        from repro.core.serialization import narrowest_int_dtype

        kb = (
            narrowest_int_dtype(key_range).itemsize
            if key_range is not None
            else 4
        )
        vb = jnp.dtype(target.table.vals.dtype).itemsize
        collective = f"all_to_all[pairs x {kb + vb}B]"
    node = MapReduceNode(
        idx=idx,
        kind=kind,
        src=src,
        source_key=source_key,
        mapper=mapper,
        reducer=red.name,
        target_kind=target_kind,
        target_desc=tdesc,
        engine_requested=engine,
        engine=resolved,
        wire=wire,
        key_range=key_range,
        env_sig=abstract_sig(env),
        collective=collective,
    )
    if hierarchical:
        apply_hierarchical(node, n_nodes)
    if resolved in ("eager", "pallas"):
        node.cost_estimate = cost_mod.node_cost(
            resolved, node_key_count(target)
        )
    node.resolved_hash = node.hash
    # A node the session supervisor already degraded stays degraded: the
    # rebuilt node resolves straight to eager and hits the executable the
    # recovery dispatch compiled (the no-cache-poisoning contract).
    if degraded and node.resolved_hash in degraded:
        degrade_node(node)
    return node


def single_op_plan(node: MapReduceNode, n_shards: int, n_nodes: int = 1) -> Plan:
    """The standalone ``map_reduce`` path: one op is a one-node plan."""
    return Plan(
        nodes=[node],
        sources=[],
        state_desc="-",
        n_shards=n_shards,
        n_nodes=n_nodes,
        passes=(),
        collectives_per_iter=1,
        collectives_unbatched=1,
    )
