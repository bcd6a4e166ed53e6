"""The kernels' sizing grids and picks, and the engine cost model.

* **Sizing grids** (``segment_block_candidates``,
  ``hash_table_candidates``): one implementation of the VMEM working-set
  arithmetic (``segment_working_set``, ``hash_working_set``) for both
  Pallas kernels, listing every launch geometry that fits ``VMEM_BUDGET``
  with its working set.
* **Picks** (``choose_block_n``, ``choose_table_cap``, ``hash_block_n``,
  ``choose_probe_depth``): the geometry each kernel launches with, the
  largest block on its grid clamped to the stream.  ``next_capacity``
  walks the hash capacities one rung at a time for overflow escalation.
* **Engine model** (``node_cost``, ``pick_engine``): how
  ``engine="auto"`` chooses between eager XLA and the Pallas kernel from
  the key count K.  Costs are in abstract accumulator-row units: the kernel
  pays ~2 rows per key (accumulate + writeback), eager ~1 row per key plus
  a fixed ``EAGER_FIXED_ROWS`` sort/lowering overhead, so the crossover is
  ``K == PALLAS_AUTO_MAX_KEYS``.

Import discipline: this module imports only jax.numpy and the standard
library, never ``repro.*``.  The kernels still import it lazily, inside the
functions that need it (``hash_combine.py``, ``segment_reduce.py``):
importing ``repro.core.cost`` runs ``repro/core/__init__``, which imports
the kernels through ``reducers.py``, so a module-level import from a kernel
would be a cycle.
"""
from __future__ import annotations

import jax.numpy as jnp

__all__ = [
    "EAGER_FIXED_ROWS",
    "PALLAS_AUTO_MAX_KEYS",
    "VMEM_BUDGET",
    "acc_dtype",
    "choose_block_n",
    "choose_probe_depth",
    "choose_table_cap",
    "hash_block_n",
    "hash_table_candidates",
    "next_capacity",
    "node_cost",
    "pick_engine",
    "segment_block_candidates",
    "segment_working_set",
    "use_matmul",
]

# Default VMEM budget for both kernels' sizing picks (bytes).  Mosaic's scoped
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
# Candidate grids + scores (one sizing model for both kernels)
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
    """Every ``block_n`` the dense kernel can launch with, with its score.

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
    """Every ``(cap, block_n)`` pair the hash kernel can launch with.

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
