"""Cost-based planning: per-node cost model, kernel-config candidate grids,
and the first-dispatch autotuning cache.

Until PR 8 the planner made its one load-bearing choice — eager XLA reduce vs
the Pallas VMEM-resident kernel — with a single static rule
(``K <= PALLAS_AUTO_MAX_KEYS``), and the two kernel autotuners
(``segment_reduce.choose_block_n``, ``hash_combine.choose_table_cap``)
duplicated the VMEM-budget arithmetic while scoring candidates with analytic
formulas that never saw a measurement.  This module closes ROADMAP open item
2 in three layers:

* **Candidate grids** (``segment_block_candidates`` /
  ``hash_table_candidates``): ONE implementation of the VMEM working-set
  arithmetic, exposing every config the greedy tuners consider together with
  its working-set score.  The kernels' ``choose_*`` functions are now thin
  argmax-style picks over these grids (bit-identical to the pre-PR-8 greedy
  loops), and the measured autotuner times a small slice of the same grid
  instead of re-deriving one.
* **Calibrated fallback model** (``node_cost`` / ``pick_engine``): the
  no-measurement engine policy.  Costs are in abstract *accumulator-row
  units*: the kernel pays ~2 rows of VMEM traffic per key (accumulate +
  writeback) while eager's segment-sort path pays ~1 row per key plus a
  fixed ``EAGER_FIXED_ROWS`` lowering/sort overhead.  The crossover is
  exactly ``K == PALLAS_AUTO_MAX_KEYS`` — the policy ``engine="auto"``
  shipped with since PR 2 — so resolution stays deterministic and the PR 2
  differential matrix keeps pinning it.
* **Measured autotuning** (``TunedConfig`` / ``TuningCache``): with
  ``tune=True`` the session times the candidate grid on the first dispatch
  of a plan and caches the winner keyed by the node's plan hash; every later
  dispatch — per-op, ``run_loop`` block, or BlazeServe query — reuses the
  measured config.  The cache is JSON-persistable beside checkpoints
  (``save``/``load``).

Import discipline: this module imports ONLY jax/numpy/stdlib — never
``repro.*`` — so the kernels (which sit *below* ``repro.core`` in the import
order) can import it at module level without a cycle.
"""
from __future__ import annotations

import dataclasses
import json
import os
import tempfile
import threading
import warnings
from typing import Iterator

import jax.numpy as jnp

__all__ = [
    "EAGER_FIXED_ROWS",
    "PALLAS_AUTO_MAX_KEYS",
    "VMEM_BUDGET",
    "TunedConfig",
    "TuningCache",
    "acc_dtype",
    "choose_block_n",
    "choose_probe_depth",
    "choose_table_cap",
    "dense_tuning_candidates",
    "hash_block_n",
    "hash_table_candidates",
    "hash_tuning_candidates",
    "next_capacity",
    "node_cost",
    "pick_engine",
    "segment_block_candidates",
    "segment_working_set",
    "use_matmul",
]

# Default VMEM budget for both kernel autotuners (bytes).  Mosaic's scoped
# VMEM limit on a v5e core is 16 MiB (the limit its compiler reports when a
# kernel overflows); the working-set models below count lane/sublane padding
# as Mosaic lays tiles out, and the budget keeps a quarter of the limit free
# for spills and semaphores.
VMEM_BUDGET = 12 * 1024 * 1024

# The fallback cost model's calibration anchor.  The kernel pays ~2
# accumulator-row units per key, eager pays ~1 unit per key plus this fixed
# sort/lowering overhead — so the modelled crossover sits at K == 4096 keys,
# the threshold ``engine="auto"`` has shipped with (and been differential-
# tested at) since PR 2.  4096 keys x 128 f32 lanes ~= 2 MB: comfortably
# VMEM-resident; beyond that eager's XLA segmented reduce wins anyway.
PALLAS_AUTO_MAX_KEYS = 4096
EAGER_FIXED_ROWS = PALLAS_AUTO_MAX_KEYS


# ---------------------------------------------------------------------------
# Strategy helpers (shared by both kernels' working-set arithmetic)
# ---------------------------------------------------------------------------


def acc_dtype(dtype):
    """Accumulator dtype: f32 for floats (bf16 upcast), i32 for ints — the
    widths the MXU/VPU natively accumulate in."""
    if jnp.issubdtype(jnp.dtype(dtype), jnp.floating):
        return jnp.float32
    return jnp.int32


def use_matmul(reducer: str, acc) -> bool:
    """One-hot-matmul (MXU) strategy applies to float sums only; everything
    else takes the select-scatter VPU fold."""
    return reducer == "sum" and acc == jnp.float32


# ---------------------------------------------------------------------------
# Candidate grids + scores (the deduplicated tuner logic)
# ---------------------------------------------------------------------------


# Pair blocks are whole 128-lane tiles (Mosaic's vector width); the grids
# below walk powers of two from one tile up to ``MAX_BLOCK``.
LANES = 128
MAX_BLOCK = 2048


def _pad(x: int, m: int) -> int:
    return -(-max(x, 1) // m) * m


def segment_working_set(
    bn: int, num_segments: int, v: int, reducer: str = "sum",
    dtype=jnp.float32,
) -> int:
    """VMEM bytes one grid step of the dense kernel holds at block ``bn``.

    Counted as Mosaic lays them out — sublanes padded to 8, lanes to 128:
    the double-buffered ``[1, bn]`` id and ``[V, bn]`` value blocks, the
    double-buffered ``[K, V]`` accumulator, and ~3 ``[K, bn]`` tiles of
    one-hot / select intermediates (the select-scatter fold adds one more
    per value column it keeps live).
    """
    k8, v8 = _pad(num_segments, 8), _pad(v, 8)
    tiles = 3 if use_matmul(reducer, acc_dtype(dtype)) else 4
    stream = 2 * (8 + v8) * bn * 4
    acc = 2 * k8 * _pad(v, LANES) * 4
    return stream + acc + tiles * k8 * bn * 4


def _block_grid(fits) -> list[int]:
    """Lane-aligned power-of-two blocks while ``fits(bn)`` holds; the
    one-tile block is always offered."""
    grid = [LANES]
    while grid[-1] < MAX_BLOCK and fits(2 * grid[-1]):
        grid.append(2 * grid[-1])
    return grid


def segment_block_candidates(
    n: int, num_segments: int, v: int, reducer: str = "sum",
    dtype=jnp.float32, vmem_budget: int = VMEM_BUDGET,
) -> list[tuple[int, int]]:
    """Every ``block_n`` the dense-kernel tuner considers, with its score.

    Returns ``[(block_n, working_set_bytes), ...]`` in ascending block order:
    power-of-two blocks from one 128-lane tile up to ``MAX_BLOCK`` whose
    ``segment_working_set`` fits the budget (the one-tile block is always
    offered).
    """
    def ws(bn):
        return segment_working_set(bn, num_segments, v, reducer, dtype)

    return [(bn, ws(bn)) for bn in _block_grid(lambda b: ws(b) <= vmem_budget)]


def choose_block_n(
    n: int, num_segments: int, v: int, reducer: str = "sum",
    dtype=jnp.float32, vmem_budget: int = VMEM_BUDGET,
) -> int:
    """Largest candidate block that fits, clamped to the stream's own
    lane-padded length."""
    bn = segment_block_candidates(
        n, num_segments, v, reducer, dtype, vmem_budget
    )[-1][0]
    return min(bn, _pad(n, LANES))


def hash_working_set(
    cap: int, bn: int, v: int, reducer: str = "sum", dtype=jnp.float32
) -> int:
    """VMEM bytes resident per probe round of the hash kernel at
    ``(cap, bn)``, padded as Mosaic lays them out: the table's input and
    output copies (``[C, 1]`` keys and ``[C, V]`` values, each at least a
    lane tile wide), the double-buffered ``[1, bn]`` / ``[V, bn]`` stream
    blocks, and ~6 ``[C, bn]`` probe tiles (one-hot, gathered keys, claim,
    match, and the deposit's select or matmul operand)."""
    c8, v8 = _pad(cap, 8), _pad(v, 8)
    table = 2 * c8 * (LANES + _pad(v, LANES)) * 4
    stream = 2 * (8 + v8) * bn * 4
    return table + stream + 6 * c8 * bn * 4


def hash_block_n(
    cap: int, n: int, v: int, reducer: str = "sum", dtype=jnp.float32,
    vmem_budget: int = VMEM_BUDGET,
) -> int:
    """Largest lane-aligned block whose probe round fits the budget at
    table capacity ``cap``, clamped to the stream's lane-padded length."""
    grid = _block_grid(
        lambda b: hash_working_set(cap, b, v, reducer, dtype) <= vmem_budget
    )
    return min(grid[-1], _pad(n, LANES))


def choose_probe_depth(n: int, table_cap: int) -> int:
    """Probe rounds to configure for ``n`` pairs into a ``table_cap`` table.

    Linear-probing cluster lengths grow with the load factor α = n/C: ~16
    probes cover α ≤ 0.5 comfortably, near-full tables need more rounds to
    *find* the free slots that do exist.
    """
    alpha = min(1.0, n / max(1, table_cap))
    if alpha <= 0.5:
        depth = 16
    elif alpha <= 0.75:
        depth = 32
    else:
        depth = 64
    return min(table_cap, depth)


def hash_table_candidates(
    n: int,
    v: int,
    reducer: str = "sum",
    dtype=jnp.float32,
    *,
    distinct_hint: int | None = None,
    vmem_budget: int = VMEM_BUDGET,
) -> list[tuple[int, int, int, int]]:
    """Every ``(cap, block_n)`` pair the hash-kernel tuner considers.

    Returns ``[(table_cap, block_n, max_probes, working_set_bytes), ...]``:
    the capacity is fixed first (load factor ≤ 0.5 over the distinct-key
    bound, power of two, shrunk until the one-tile block fits the budget),
    then every lane-aligned power-of-two block that fits is offered.
    """
    distinct = min(n, distinct_hint) if distinct_hint else n
    cap = 128
    while cap < 2 * max(1, distinct) and cap < (1 << 20):
        cap *= 2

    def fits(cap_: int, bn_: int) -> bool:
        return hash_working_set(cap_, bn_, v, reducer, dtype) <= vmem_budget

    while cap > 128 and not fits(cap, LANES):
        cap //= 2
    probes = choose_probe_depth(n, cap)
    return [
        (cap, bn, probes, hash_working_set(cap, bn, v, reducer, dtype))
        for bn in _block_grid(lambda b: fits(cap, b))
    ]


def choose_table_cap(
    n: int,
    v: int,
    reducer: str = "sum",
    dtype=jnp.float32,
    *,
    distinct_hint: int | None = None,
    vmem_budget: int = VMEM_BUDGET,
) -> tuple[int, int, int]:
    """(table_cap, block_n, max_probes): the largest-block candidate from the
    shared grid, clamped to the stream's lane-padded length."""
    cap, bn, probes, _ = hash_table_candidates(
        n, v, reducer, dtype, distinct_hint=distinct_hint,
        vmem_budget=vmem_budget,
    )[-1]
    return cap, min(bn, _pad(n, LANES)), probes


def next_capacity(cap: int, *, limit: int = 1 << 20) -> int | None:
    """The next rung of the hash-capacity grid above ``cap``.

    The grid is the same one ``hash_table_candidates`` walks: powers of two
    from 128 up to ``limit``.  Overflow escalation climbs it one rung per
    re-dispatch; ``None`` means the grid is exhausted and the supervisor must
    stop escalating (overflow stays counted, as before).
    """
    if cap >= limit:
        return None
    nxt = 128
    while nxt <= cap:
        nxt *= 2
    return min(nxt, limit)


# ---------------------------------------------------------------------------
# Calibrated fallback model (the no-measurement engine policy)
# ---------------------------------------------------------------------------


def node_cost(engine: str, k: int) -> float:
    """Modelled cost of one shard-local combine over ``k`` accumulator rows,
    in abstract accumulator-row units.

    ``pallas``: the VMEM kernel touches every accumulator row roughly twice
    per pass (monoid accumulate + final writeback) → ``2k``.  ``eager``: the
    XLA segmented reduce touches each row once but pays a fixed
    sort/lowering overhead (``EAGER_FIXED_ROWS``) regardless of ``k`` →
    ``k + EAGER_FIXED_ROWS``.  ``naive`` ships raw pairs and re-reduces
    everywhere — modelled as an order of magnitude over eager.
    """
    if engine == "pallas":
        return 2.0 * k
    if engine == "naive":
        return 10.0 * (k + EAGER_FIXED_ROWS)
    return float(k) + EAGER_FIXED_ROWS


def pick_engine(k: int) -> str:
    """The fallback resolution for ``engine="auto"``: the modelled-cheaper
    engine, eager when ``k`` is unknown (``k <= 0``).  The calibration makes
    the crossover exactly ``k == PALLAS_AUTO_MAX_KEYS``, preserving the PR 2
    policy bit-for-bit."""
    if k <= 0:
        return "eager"
    return "pallas" if node_cost("pallas", k) <= node_cost("eager", k) else "eager"


# ---------------------------------------------------------------------------
# Measured autotuning: configs, candidate enumeration, cache
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TunedConfig:
    """One execution config for a MapReduce node — a measurement candidate,
    and (once timed) the cached winner.

    ``wall_s`` and ``source`` are measurement *outcomes*, excluded from
    equality/hash so a config's identity — and with it the executable-cache
    key it participates in — depends only on what actually lowers.
    """

    engine: str  # "eager" | "pallas"
    block_n: int | None = None  # dense/hash kernel block override
    table_cap: int | None = None  # hash kernel: capacity override
    probe_depth: int | None = None  # hash kernel: probe rounds override
    source: str = dataclasses.field(default="fallback", compare=False)
    wall_s: float | None = dataclasses.field(default=None, compare=False)

    def describe(self) -> str:
        parts = [self.engine]
        if self.table_cap:
            parts.append(f"cap={self.table_cap}")
        if self.block_n:
            parts.append(f"bn={self.block_n}")
        if self.probe_depth:
            parts.append(f"probes={self.probe_depth}")
        return " ".join(parts)

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "TunedConfig":
        fields = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in fields})


def dense_tuning_candidates(
    k: int, v: int, reducer: str, dtype, *, vmem_budget: int = VMEM_BUDGET,
) -> list[TunedConfig]:
    """The measurement grid for a dense-target node: eager, the kernel at
    its analytic default block, and the kernel one block step down/up the
    shared candidate frontier.  Every candidate reduces with the same monoid
    over the same pairs — results are bit-identical for exact inputs."""
    cands = [TunedConfig(engine="eager")]
    grid = [bn for bn, _ in segment_block_candidates(
        1 << 30, k, v, reducer, dtype, vmem_budget
    )]
    default = grid[-1]
    picks = [default]
    if default // 2 in grid:
        picks.append(default // 2)
    if default // 4 in grid:
        picks.append(default // 4)
    cands += [TunedConfig(engine="pallas", block_n=bn) for bn in picks]
    return cands


def hash_tuning_candidates(
    v: int, reducer: str, dtype, *, key_range: int | None,
    vmem_budget: int = VMEM_BUDGET,
) -> list[TunedConfig]:
    """The measurement grid for a hash-target node.

    With a ``key_range`` the distinct-key bound is known statically, so full
    ``(cap, block_n, probes)`` triples off the shared grid are safe to pin
    (capacity stays ≥ 2x the distinct bound — no overflow risk, results stay
    bit-identical across candidates).  Without one, capacity must follow the
    runtime stream length, so only the engine is tuned and the in-stage
    analytic tuner keeps picking the kernel config.
    """
    cands = [TunedConfig(engine="eager")]
    if key_range is None:
        cands.append(TunedConfig(engine="pallas"))
        return cands
    grid = hash_table_candidates(
        1 << 30, v, reducer, dtype, distinct_hint=key_range,
        vmem_budget=vmem_budget,
    )
    seen: set[tuple] = set()
    for cap, bn, probes, _ in (grid[-1], grid[len(grid) // 2], grid[0]):
        if (cap, bn) in seen:
            continue
        seen.add((cap, bn))
        cands.append(TunedConfig(
            engine="pallas", block_n=bn, table_cap=cap, probe_depth=probes
        ))
    return cands


class TuningCache:
    """Measured winners keyed by node plan-hash (``MapReduceNode.tune_key``).

    Thread-safe (BlazeServe prepares plans under concurrent submissions).
    ``measurements`` counts candidate timings performed, ``hits``/``misses``
    count lookups — the counters the measure-exactly-once tests pin.
    """

    def __init__(self) -> None:
        self._entries: dict[str, TunedConfig] = {}
        self._lock = threading.Lock()
        self.measurements = 0
        self.hits = 0
        self.misses = 0

    def get(self, key: str) -> TunedConfig | None:
        with self._lock:
            cfg = self._entries.get(key)
            if cfg is None:
                self.misses += 1
            else:
                self.hits += 1
            return cfg

    def peek(self, key: str) -> TunedConfig | None:
        with self._lock:
            return self._entries.get(key)

    def put(self, key: str, cfg: TunedConfig) -> None:
        with self._lock:
            self._entries[key] = cfg

    def record_measurements(self, n: int) -> None:
        with self._lock:
            self.measurements += n

    def __contains__(self, key: str) -> bool:
        with self._lock:
            return key in self._entries

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def items(self) -> Iterator[tuple[str, TunedConfig]]:
        with self._lock:
            return iter(sorted(self._entries.items()))

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "entries": len(self._entries),
                "measurements": self.measurements,
                "hits": self.hits,
                "misses": self.misses,
                "configs": {
                    k: cfg.to_dict()
                    for k, cfg in sorted(self._entries.items())
                },
            }

    # -- persistence (beside checkpoints) -----------------------------------

    def save(self, path: str) -> None:
        """Atomic JSON dump (tmp + rename, same discipline as checkpoints)."""
        doc = {
            "version": 1,
            "entries": {
                k: cfg.to_dict() for k, cfg in sorted(self._entries.items())
            },
        }
        d = os.path.dirname(os.path.abspath(path)) or "."
        fd, tmp = tempfile.mkstemp(dir=d, prefix=".tuning-")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(doc, f, indent=1, sort_keys=True)
                # fsync before the rename: os.replace orders the directory
                # entry, not the data blocks — without the sync a crash can
                # commit a truncated file under the final name.
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
        except BaseException:
            if os.path.exists(tmp):
                os.unlink(tmp)
            raise

    def load(self, path: str) -> int:
        """Merge entries from ``path`` (loaded winners keep their recorded
        ``source``/``wall_s``); returns how many were loaded.

        A truncated, corrupt, or otherwise unreadable cache is a warning,
        not a crash: tuning is an optimisation, so the session starts with
        whatever loaded (usually nothing) and re-measures on demand.
        """
        try:
            with open(path) as f:
                doc = json.load(f)
            entries = doc.get("entries", {})
            items = [
                (k, TunedConfig.from_dict(d)) for k, d in entries.items()
            ]
        except (OSError, ValueError, TypeError, UnicodeDecodeError) as e:
            warnings.warn(
                f"ignoring unreadable tuning cache {path!r}: {e}",
                RuntimeWarning,
                stacklevel=2,
            )
            return 0
        with self._lock:
            for k, cfg in items:
                self._entries[k] = cfg
        return len(items)
