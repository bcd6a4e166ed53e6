"""Blaze distributed containers, adapted to SPMD JAX.

The paper's three containers map onto sharded ``jax.Array``s:

* ``DistRange``   — start/stop/step only; local values are synthesised from
                    ``iota`` + the device's mesh coordinate (no storage, as in
                    the paper).
* ``DistVector``  — an array sharded on axis 0 over the ``data`` mesh axis,
                    with ``foreach``, ``topk`` (O(n + k log k) time, O(k·shards)
                    wire bytes), and ``distribute``/``collect`` conversions.
* ``DistHashMap`` — a fixed-capacity open-addressing (linear probing) table
                    per shard.  XLA needs static shapes, so the dynamic C++
                    hash map becomes a capacity-bounded table with fully
                    vectorised round-based probing (see ``hashmap_insert``).

Everything here is pure-functional: containers are pytrees, and all mutation
returns new containers.
"""
from __future__ import annotations

import dataclasses
import zlib
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.reducers import Reducer, get_reducer

Array = jax.Array

EMPTY_KEY = np.iinfo(np.int32).min  # open-addressing "slot free" sentinel
DATA_AXIS = "data"
NODE_AXIS = "node"


# ---------------------------------------------------------------------------
# Mesh helpers
#
# Containers shard their leading dim over ALL data-parallel mesh axes: the
# 1-D ``("data",)`` mesh of a single host, or the 2-D ``("node", "data")``
# mesh of a multi-host launch (``repro.launch.mesh.make_node_data_mesh``),
# where ``node`` is the slow inter-host axis and ``data`` the fast
# intra-host axis.  Shard indices are flattened node-major: shard
# ``node_idx * n_data + data_idx``.
# ---------------------------------------------------------------------------


def data_mesh(n_devices: int | None = None) -> Mesh:
    """A 1-D mesh over (up to) all visible devices, axis name ``data``."""
    devs = jax.devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    return Mesh(np.asarray(devs), (DATA_AXIS,))


def data_axes(mesh: Mesh) -> tuple[str, ...]:
    """Mesh axes container leading dims shard over, slowest (node) first."""
    if NODE_AXIS in mesh.axis_names:
        return (NODE_AXIS, DATA_AXIS)
    return (DATA_AXIS,)


def data_pspec(mesh: Mesh) -> P:
    """PartitionSpec sharding a leading dim over every data-parallel axis."""
    axes = data_axes(mesh)
    return P(axes) if len(axes) > 1 else P(DATA_AXIS)


def n_nodes(mesh: Mesh) -> int:
    """Simulated/real host count: the ``node`` axis size (1 on 1-D meshes)."""
    return mesh.shape[NODE_AXIS] if NODE_AXIS in mesh.axis_names else 1


def _nshards(mesh: Mesh) -> int:
    n = 1
    for ax in data_axes(mesh):
        n *= mesh.shape[ax]
    return n


def shard_count(mesh: Mesh) -> int:
    """Total data-parallel shards: product over ``data_axes(mesh)``."""
    return _nshards(mesh)


# ---------------------------------------------------------------------------
# Hashing (splitmix32 finaliser — cheap, good avalanche, uint32-wrap native)
# ---------------------------------------------------------------------------


def hash32(x: Array) -> Array:
    """Vectorised splitmix32-style integer hash → uint32."""
    x = x.astype(jnp.uint32)
    x = (x ^ (x >> 16)) * jnp.uint32(0x7FEB352D)
    x = (x ^ (x >> 15)) * jnp.uint32(0x846CA68B)
    return x ^ (x >> 16)


def shard_of_key(keys: Array, n_shards: int) -> Array:
    """Ownership partition: which shard owns each key (high bits of the hash)."""
    return (hash32(keys) >> 16) % jnp.uint32(n_shards)


# ---------------------------------------------------------------------------
# Eager local combine: sort + segmented scan, first-class (paper §2.3.1)
# ---------------------------------------------------------------------------


def unique_combine(
    keys: Array, vals: Array, mask: Array, reducer: Reducer
) -> tuple[Array, Array, Array]:
    """Combine duplicate keys locally; returns same-length (keys, vals, valid).

    Sorts live entries first (by key), runs a segmented inclusive scan with
    the reducer's combine, and keeps only the last element of each run.
    Masked-out or duplicate slots come back with ``key == EMPTY_KEY`` and
    ``valid == False``.  This is the device-local *eager reduction*
    primitive: it is applied before any bytes go on the wire.

    The mask rides through the sort as its own lexsort column instead of
    being encoded into the key: the old ``key := INT32_MAX if masked``
    encoding conflated genuine ``INT32_MAX`` keys with masked-out slots
    (folding garbage values into their run), and a genuine ``EMPTY_KEY``
    key is now emitted with ``valid == True`` — ``valid``, not the key
    value, is the liveness contract for downstream consumers.
    """
    n = keys.shape[0]
    if n == 0:
        return keys, vals, mask
    # Live entries first (sorted by key), masked entries at the end.  The
    # mask is a sort column, so no key VALUE can collide with the "masked"
    # encoding.
    order = jnp.lexsort((keys, ~mask))
    skeys = jnp.take(keys, order)
    svals = jnp.take(vals, order, axis=0)
    smask = jnp.take(mask, order)

    # Segment boundaries: key change, live/masked transition, and every
    # masked slot is its own segment (masked keys are unsorted garbage —
    # never fold them together or into a live run).
    newseg = (skeys[1:] != skeys[:-1]) | (smask[1:] != smask[:-1]) | ~smask[1:]
    starts = jnp.concatenate([jnp.ones((1,), bool), newseg])

    def op(a, b):
        av, af = a
        bv, bf = b
        bcast = bf.reshape(bf.shape + (1,) * (av.ndim - bf.ndim))
        return jnp.where(bcast, bv, reducer.combine(av, bv)), af | bf

    scanned, _ = jax.lax.associative_scan(op, (svals, starts), axis=0)
    is_last = jnp.concatenate([newseg, jnp.ones((1,), bool)])
    valid = is_last & smask
    out_keys = jnp.where(valid, skeys, EMPTY_KEY)
    ident = reducer.identity(vals.dtype)
    vb = valid.reshape(valid.shape + (1,) * (svals.ndim - 1))
    out_vals = jnp.where(vb, scanned, ident)
    return out_keys, out_vals, valid


# ---------------------------------------------------------------------------
# DistHashMap: static-capacity open addressing with round-based probing
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class HashTable:
    """One shard's table. ``keys[C]`` int32 (EMPTY_KEY = free), ``vals[C, ...]``."""

    keys: Array
    vals: Array
    overflow: Array  # scalar int32: #pairs dropped because probing exhausted

    @property
    def capacity(self) -> int:
        return self.keys.shape[0]


def make_table(capacity: int, val_shape: tuple, val_dtype, reducer: Reducer) -> HashTable:
    return HashTable(
        keys=jnp.full((capacity,), EMPTY_KEY, jnp.int32),
        vals=jnp.full((capacity,) + tuple(val_shape), reducer.identity(val_dtype), val_dtype),
        overflow=jnp.zeros((), jnp.int32),
    )


def hashmap_insert(
    table: HashTable,
    keys: Array,
    vals: Array,
    valid: Array,
    reducer: Reducer,
    max_probes: int = 16,
) -> HashTable:
    """Insert/merge a batch of pairs with *unique* keys into the table.

    Vectorised linear probing, one scatter round per probe distance:

      round r:  slot_i = (h_i + r) mod C for every unplaced pair i
        1. pairs whose key already sits at slot_i deposit (gather-combine-set,
           safe because batch keys are unique: ≤1 pair matches a slot),
        2. pairs whose slot is FREE race to claim it via scatter-max on the
           hashed key (deterministic winner); winners deposit next round
           re-check (their key is now at the slot),
        3. losers continue to round r+1.

    Callers must pre-combine duplicates (``unique_combine``) — that is the
    eager-reduction invariant, so it is free by construction.
    """
    return hashmap_insert_rounds(table, keys, vals, valid, reducer, max_probes)[0]


def hashmap_insert_rounds(
    table: HashTable,
    keys: Array,
    vals: Array,
    valid: Array,
    reducer: Reducer,
    max_probes: int = 16,
) -> tuple[HashTable, Array]:
    """``hashmap_insert`` that also returns the probe rounds it ran.

    The rounds run in a ``while_loop`` that stops once every valid pair is
    placed, or after ``max_probes`` rounds; pairs still unplaced then are
    counted into ``overflow``.  A round with no unplaced pair changes
    nothing, so the table is the one ``max_probes`` full rounds would give.
    The round count is an int32 scalar on the device.
    """
    cap = table.capacity
    h = (hash32(keys) % jnp.uint32(cap)).astype(jnp.int32)

    def unfinished(state):
        r, _, _, active = state
        return (r < max_probes) & jnp.any(active)

    def round_body(state):
        r, tkeys, tvals, active = state
        slot = ((h + r) % cap).astype(jnp.int32)
        slot_key = jnp.take(tkeys, slot)

        # (2) claim free slots: scatter-max of (key ^ sign) — any deterministic
        # tie-break works; we use max of the raw key with EMPTY_KEY as floor.
        want = active & (slot_key == EMPTY_KEY)
        claim = jnp.full((cap,), EMPTY_KEY, jnp.int32)
        claim = claim.at[jnp.where(want, slot, cap)].max(
            jnp.where(want, keys, EMPTY_KEY), mode="drop"
        )
        tkeys = jnp.where(claim != EMPTY_KEY, claim, tkeys)

        # (1)+(2) deposit where our key is now resident at our slot.
        slot_key = jnp.take(tkeys, slot)
        deposit = active & (slot_key == keys)
        cur = jnp.take(tvals, slot, axis=0)
        merged = reducer.combine(cur, vals)
        db = deposit.reshape(deposit.shape + (1,) * (vals.ndim - 1))
        new_at_slot = jnp.where(db, merged, cur)
        tvals = tvals.at[jnp.where(deposit, slot, cap)].set(new_at_slot, mode="drop")

        active = active & ~deposit
        return r + 1, tkeys, tvals, active

    rounds, tkeys, tvals, active = jax.lax.while_loop(
        unfinished, round_body,
        (jnp.zeros((), jnp.int32), table.keys, table.vals, valid),
    )
    overflow = table.overflow + jnp.sum(active).astype(jnp.int32)
    return HashTable(tkeys, tvals, overflow), rounds


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class DistHashMap:
    """Distributed hash map: one ``HashTable`` shard per device on ``data``.

    ``table.keys``/``table.vals`` have a leading [n_shards] dim sharded over
    the data axis.  Key ownership: ``shard_of_key(k, n_shards)``.
    """

    table: HashTable
    reducer_name: str = dataclasses.field(metadata=dict(static=True))

    @property
    def capacity_per_shard(self) -> int:
        return self.table.keys.shape[-1]

    @property
    def n_shards(self) -> int:
        return self.table.keys.shape[0]

    def items(self) -> tuple[np.ndarray, np.ndarray]:
        """Live entries as host arrays ``(keys [n], vals [n, ...])``.

        Fully vectorised (one mask + ``flatnonzero`` over the flattened
        table — no Python loop over slots), so benchmarks and bulk consumers
        can take the arrays directly instead of round-tripping a dict.
        Entry order is table order, not key order.
        """
        keys = np.asarray(jax.device_get(self.table.keys)).reshape(-1)
        vals = np.asarray(jax.device_get(self.table.vals))
        vals = vals.reshape((-1,) + vals.shape[2:])
        live = np.flatnonzero(keys != EMPTY_KEY)
        return keys[live], vals[live]

    def to_dict(self) -> dict[int, np.ndarray]:
        """Host-side materialisation (the paper's ``collect``)."""
        keys, vals = self.items()
        return dict(zip(keys.tolist(), vals))

    def size(self) -> int:
        keys = np.asarray(jax.device_get(self.table.keys))
        return int((keys != EMPTY_KEY).sum())

    def total_overflow(self) -> int:
        return int(np.asarray(jax.device_get(self.table.overflow)).sum())


def make_dist_hashmap(
    mesh: Mesh,
    capacity_per_shard: int,
    val_shape: tuple = (),
    val_dtype=jnp.float32,
    reducer: str | Reducer = "sum",
) -> DistHashMap:
    red = get_reducer(reducer)
    n = _nshards(mesh)
    sharding = NamedSharding(mesh, data_pspec(mesh))
    keys = jax.device_put(
        jnp.full((n, capacity_per_shard), EMPTY_KEY, jnp.int32), sharding
    )
    vals = jax.device_put(
        jnp.full(
            (n, capacity_per_shard) + tuple(val_shape),
            red.identity(val_dtype),
            val_dtype,
        ),
        sharding,
    )
    overflow = jax.device_put(jnp.zeros((n,), jnp.int32), sharding)
    return DistHashMap(
        HashTable(keys, vals, overflow), reducer_name=red.name
    )


# ---------------------------------------------------------------------------
# DistRange / DistVector
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class DistRange:
    """start/stop/step — no storage; shards synthesise their local subrange."""

    start: int = dataclasses.field(metadata=dict(static=True))
    stop: int = dataclasses.field(metadata=dict(static=True))
    step: int = dataclasses.field(metadata=dict(static=True))

    def __len__(self) -> int:
        return max(0, -(-(self.stop - self.start) // self.step))

    def local_values(self, shard_idx: Array, n_shards: int) -> tuple[Array, Array]:
        """(values, valid) for this shard: contiguous block partitioning."""
        n = len(self)
        per = -(-n // n_shards)
        local_i = jnp.arange(per) + shard_idx * per
        valid = local_i < n
        vals = self.start + local_i * self.step
        return vals.astype(jnp.int32), valid


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class DistVector:
    """Array sharded on axis 0 across ``data``; ``n`` true (pre-pad) length."""

    data: Array
    n: int = dataclasses.field(metadata=dict(static=True))

    def __len__(self) -> int:
        return self.n

    def local_mask(self, shard_idx: Array, n_shards: int) -> Array:
        per = self.data.shape[0] // n_shards
        idx = jnp.arange(per) + shard_idx * per
        return idx < self.n


def distribute(x: np.ndarray | Array, mesh: Mesh | None = None) -> DistVector:
    """Paper's ``distribute``: host array → DistVector (pads to shard multiple)."""
    mesh = mesh or data_mesh()
    x = np.asarray(x)
    n = x.shape[0]
    shards = _nshards(mesh)
    pad = (-n) % shards
    if pad:
        x = np.concatenate([x, np.zeros((pad,) + x.shape[1:], x.dtype)], axis=0)
    arr = jax.device_put(x, NamedSharding(mesh, data_pspec(mesh)))
    return DistVector(arr, n)


def collect(v: DistVector) -> np.ndarray:
    """Paper's ``collect``: DistVector → host array (drops padding)."""
    return np.asarray(jax.device_get(v.data))[: v.n]


_FOREACH_CACHE: dict = {}


def foreach(v: DistVector, fn: Callable, env=None) -> DistVector:
    """Apply ``fn`` to each element in parallel (may mutate the element).

    ``fn(x)`` or ``fn(x, env)`` — iteration-varying state goes through ``env``
    so a single compiled executable serves every iteration (same contract as
    ``map_reduce``).
    """
    env_sig = "|".join(
        f"{getattr(x, 'shape', ())}{getattr(x, 'dtype', type(x))}"
        for x in jax.tree.leaves(env)
    )
    key = (fn, v.data.shape, str(v.data.dtype), env is None, env_sig)
    if key not in _FOREACH_CACHE:
        if env is None:
            _FOREACH_CACHE[key] = jax.jit(lambda d, e: jax.vmap(fn)(d))
        else:
            _FOREACH_CACHE[key] = jax.jit(
                lambda d, e: jax.vmap(lambda x: fn(x, e))(d)
            )
    out = _FOREACH_CACHE[key](v.data, env)
    return DistVector(out, v.n)


_TOPK_CACHE: dict = {}
_TOPK_CACHE_MAX = 64  # fresh-closure callers evict oldest instead of leaking


def _topk_local(score_fn, kk: int, shards: int, has_env: bool):
    """Memoized per-shard top-k executable.

    The old implementation built a fresh ``@jax.jit`` closure on every call,
    so every ``topk`` re-traced and re-compiled.  The executable is keyed on
    everything that shapes the plan — ``(score_fn, kk, shards, has_env)``
    here plus jit's own signature on the operand shapes; ``nvalid`` and
    ``env`` are traced operands, so varying ``v.n`` or the query does not
    retrace.  Repeated calls are dispatch-only (asserted in
    ``tests/test_program.py``).
    """
    key = (score_fn, kk, shards, has_env)
    if key not in _TOPK_CACHE:
        if len(_TOPK_CACHE) >= _TOPK_CACHE_MAX:
            _TOPK_CACHE.pop(next(iter(_TOPK_CACHE)))

        @jax.jit
        def _local(data, nvalid, env):
            def per_shard(x, base):
                if score_fn is None:
                    scores = x.astype(jnp.float32)
                elif has_env:
                    scores = jax.vmap(lambda r: score_fn(r, env))(x)
                else:
                    scores = jax.vmap(score_fn)(x)
                idx_in = jnp.arange(x.shape[0]) + base
                scores = jnp.where(idx_in < nvalid, scores, -jnp.inf)
                s, i = jax.lax.top_k(scores, kk)
                return s, jnp.take(x, i, axis=0)

            per = data.shape[0] // shards
            xs = data.reshape((shards, per) + data.shape[1:])
            bases = jnp.arange(shards) * per
            return jax.vmap(per_shard)(xs, bases)

        _TOPK_CACHE[key] = _local
    return _TOPK_CACHE[key]


def topk(
    v: DistVector,
    k: int,
    score_fn: Callable[..., Array] | None = None,
    mesh: Mesh | None = None,
    env=None,
) -> np.ndarray:
    """Paper's DistVector.topk: local top-k per shard, then top-k of candidates.

    O(n + k log k) work and O(k · n_shards) wire bytes — the shuffle moves only
    locally-selected candidates, never the full vector (eager reduction again,
    with ``top_k`` as the monoid).  The local-selection executable is memoized
    (``_topk_local``): callers compile once per (shape, dtype, k, score_fn)
    configuration.  As with ``foreach``/``map_reduce``, call-varying state
    (the kNN query point) goes through ``env`` — ``score_fn(x, env)`` — so a
    static module-level ``score_fn`` keeps the executable cached across
    queries.
    """
    mesh = mesh or data_mesh()
    shards = _nshards(mesh)
    kk = min(k, v.data.shape[0] // shards)

    fn = _topk_local(score_fn, kk, shards, env is not None)
    s, cand = fn(v.data, jnp.int32(v.n), env)
    s = np.asarray(jax.device_get(s)).reshape(-1)
    cand = np.asarray(jax.device_get(cand))
    cand = cand.reshape((-1,) + cand.shape[2:])
    order = np.argsort(-s)[:k]
    return cand[order]


# ---------------------------------------------------------------------------
# Out-of-core: chunked shards as host-resident byte-provider blocks
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class BlockView:
    """One device-resident block of a :class:`ChunkedDistVector`.

    ``data`` is the block's rows, padded to ``block_rows`` and sharded on
    axis 0 over ``data``; ``base`` is a *traced* int32 scalar holding the
    block's global row offset (traced so every block reuses one compiled
    executable); ``n`` is the TOTAL true row count of the parent dataset —
    mappers see global indices and ``idx < n`` masks block padding exactly
    like ``DistVector`` padding.
    """

    data: Array
    base: Array
    n: int = dataclasses.field(metadata=dict(static=True))

    def __len__(self) -> int:
        return self.n


class HostBlockStore:
    """Byte-provider for chunked shards: host blocks, optional zlib
    compression, and LRU spill of cold blocks to disk.

    Blocks are stored encoded (raw ``ndarray`` or zlib bytes).  With a
    ``spill`` target (a ``repro.checkpoint.manager.BlockStore``) and a
    ``max_resident`` bound, only the hottest ``max_resident`` blocks stay in
    host memory; colder ones live on disk and are re-read on demand.  All
    blocks share one (shape, dtype) so bytes decode without per-block
    metadata.
    """

    def __init__(
        self,
        blocks: list[np.ndarray],
        *,
        compress: bool = False,
        spill=None,
        max_resident: int | None = None,
    ):
        if not blocks:
            raise ValueError("HostBlockStore needs at least one block")
        self.block_shape = blocks[0].shape
        self.dtype = blocks[0].dtype
        for b in blocks:
            if b.shape != self.block_shape or b.dtype != self.dtype:
                raise ValueError("all blocks must share one shape/dtype")
        self.compress = compress
        self.spill = spill
        self.max_resident = max_resident
        self.n_blocks = len(blocks)
        # counters (read via ChunkedDistVector.stats())
        self.loads_from_disk = 0
        self.decompressions = 0
        self.spill_bytes = 0
        self.compressed_bytes = 0
        self.raw_bytes = sum(int(b.nbytes) for b in blocks)
        self._resident: dict[int, Any] = {}  # insertion order == LRU order
        for i, b in enumerate(blocks):
            self._admit(i, self._encode(b))

    def _encode(self, arr: np.ndarray):
        if self.compress:
            payload = zlib.compress(np.ascontiguousarray(arr).tobytes(), 1)
            self.compressed_bytes += len(payload)
            return payload
        return arr

    def _payload_bytes(self, payload) -> bytes:
        if isinstance(payload, bytes):
            return payload
        return np.ascontiguousarray(payload).tobytes()

    def _admit(self, i: int, payload):
        self._resident[i] = payload
        if self.max_resident is None or self.spill is None:
            return
        while len(self._resident) > max(1, self.max_resident):
            victim, vpayload = next(iter(self._resident.items()))
            del self._resident[victim]
            if not self.spill.has(f"block_{victim:06d}"):
                self.spill_bytes += self.spill.put(
                    f"block_{victim:06d}", self._payload_bytes(vpayload)
                )

    def get(self, i: int) -> np.ndarray:
        """Block ``i`` as a host array (loading/decompressing as needed)."""
        if i in self._resident:
            payload = self._resident.pop(i)
            self._resident[i] = payload  # refresh LRU position
        else:
            self.loads_from_disk += 1
            raw = self.spill.get(f"block_{i:06d}")
            payload = raw if self.compress else np.frombuffer(
                raw, dtype=self.dtype
            ).reshape(self.block_shape)
            self._admit(i, payload)
        if self.compress:
            self.decompressions += 1
            raw = zlib.decompress(self._payload_bytes(payload))
            return np.frombuffer(raw, dtype=self.dtype).reshape(self.block_shape)
        return payload

    def stats(self) -> dict:
        return {
            "n_blocks": self.n_blocks,
            "raw_bytes": self.raw_bytes,
            "compressed_bytes": self.compressed_bytes if self.compress else 0,
            "spill_bytes": self.spill_bytes,
            "loads_from_disk": self.loads_from_disk,
            "decompressions": self.decompressions,
            "resident_blocks": len(self._resident),
        }


class ChunkedDistVector:
    """Out-of-core ``DistVector``: shards are sequences of host blocks.

    The device never holds more than one block at a time.  Streaming
    consumers (``session.map_reduce`` with a chunked source, or
    ``program.run_stream``) dispatch one compiled executable per block —
    eager reduction *per block* — while the next block is prefetched on a
    background thread (``repro.data.pipeline.prefetch_iter``).

    Not a pytree: this is a host-side container.  ``block_view(b)`` yields
    the pytree :class:`BlockView` that actually enters compiled code.
    """

    def __init__(
        self,
        provider: HostBlockStore,
        n: int,
        block_rows: int,
        mesh: Mesh | None = None,
    ):
        self.provider = provider
        self.n = n
        self.block_rows = block_rows
        self.mesh = mesh or data_mesh()
        if block_rows % _nshards(self.mesh):
            raise ValueError(
                f"block_rows={block_rows} must be a multiple of "
                f"{_nshards(self.mesh)} shards"
            )

    # -- construction -------------------------------------------------------

    @classmethod
    def from_array(
        cls,
        x: np.ndarray,
        block_rows: int,
        mesh: Mesh | None = None,
        *,
        compress: bool = False,
        spill_dir: str | None = None,
        max_resident: int | None = None,
    ) -> "ChunkedDistVector":
        """Split a host array into blocks (pads block_rows to a shard
        multiple and the last block with zeros)."""
        mesh = mesh or data_mesh()
        if block_rows <= 0:
            raise ValueError(f"block_rows must be positive, got {block_rows}")
        x = np.asarray(x)
        n = x.shape[0]
        shards = _nshards(mesh)
        block_rows = max(shards, -(-block_rows // shards) * shards)
        n_blocks = max(1, -(-n // block_rows))
        blocks = []
        for b in range(n_blocks):
            blk = x[b * block_rows : (b + 1) * block_rows]
            if blk.shape[0] < block_rows:
                pad = np.zeros(
                    (block_rows - blk.shape[0],) + x.shape[1:], x.dtype
                )
                blk = np.concatenate([blk, pad], axis=0)
            blocks.append(np.ascontiguousarray(blk))
        spill = None
        if spill_dir is not None:
            from repro.checkpoint.manager import BlockStore

            spill = BlockStore(spill_dir)
        provider = HostBlockStore(
            blocks, compress=compress, spill=spill, max_resident=max_resident
        )
        return cls(provider, n, block_rows, mesh)

    # -- geometry ------------------------------------------------------------

    @property
    def n_blocks(self) -> int:
        return self.provider.n_blocks

    @property
    def shape_tail(self) -> tuple:
        return tuple(self.provider.block_shape[1:])

    @property
    def dtype(self):
        return self.provider.dtype

    @property
    def block_nbytes(self) -> int:
        return int(
            self.block_rows
            * int(np.prod(self.shape_tail, dtype=np.int64) or 1)
            * np.dtype(self.dtype).itemsize
        )

    def __len__(self) -> int:
        return self.n

    def block_true_rows(self, b: int) -> int:
        return max(0, min(self.block_rows, self.n - b * self.block_rows))

    # -- access --------------------------------------------------------------

    def block_host(self, b: int) -> np.ndarray:
        return self.provider.get(b)

    def block_view(self, b: int, mesh: Mesh | None = None) -> BlockView:
        """Transfer block ``b`` to the device(s), sharded over ``data``."""
        mesh = mesh or self.mesh
        data = jax.device_put(
            self.block_host(b), NamedSharding(mesh, data_pspec(mesh))
        )
        base = jnp.asarray(b * self.block_rows, jnp.int32)
        return BlockView(data=data, base=base, n=self.n)

    def collect(self) -> np.ndarray:
        """Host materialisation (drops padding) — small datasets/tests."""
        out = np.concatenate(
            [self.block_host(b) for b in range(self.n_blocks)], axis=0
        )
        return out[: self.n]

    def stats(self) -> dict:
        return self.provider.stats()


def chunked(
    x: np.ndarray,
    block_rows: int,
    mesh: Mesh | None = None,
    *,
    compress: bool = False,
    spill_dir: str | None = None,
    max_resident: int | None = None,
) -> ChunkedDistVector:
    """Paper's ``distribute`` for datasets that don't fit on device: host
    array → chunked blocks streamed one at a time (see ChunkedDistVector)."""
    return ChunkedDistVector.from_array(
        x,
        block_rows,
        mesh,
        compress=compress,
        spill_dir=spill_dir,
        max_resident=max_resident,
    )
