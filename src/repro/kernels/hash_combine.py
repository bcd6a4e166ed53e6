"""Pallas hash-aggregation: eager reduction for *unbounded* key ranges.

The segment-reduce kernel (``segment_reduce.py``) is the paper's §2.3.3
small-fixed-key-range accumulator: key == index into a dense ``[K, V]`` VMEM
tile.  Word-count-shaped workloads break its premise — the key space is open
(any int32 word id) — and the hash path previously paid for it three times per
MapReduce: a sort-based ``unique_combine`` before the shuffle, another one
after it, and a scatter probe loop (``hashmap_insert``) to merge into the
target table.

``hash_aggregate`` replaces all three with ONE streaming pass: an
open-addressing (linear probing) hash table — ``keys [C]`` + ``vals [C, V]``
— resident in VMEM for the whole pass, fed pair-blocks by the grid.  Per
block, per probe round:

1. every unplaced lane computes its slot ``(h + r) mod C`` and *gathers* the
   resident key via a one-hot max over the table axis (no dynamic indexing);
2. lanes whose slot is FREE race to claim it — the winner is the max key
   among claimants (deterministic, matches ``containers.hashmap_insert``);
3. lanes whose key is now resident at their slot *deposit*: the block's
   contributions are folded into the table rows with the reducer monoid —
   a one-hot matmul on the MXU for float sums, a select-scatter VPU fold for
   min/max/prod and exact integer sums (the same two strategies as
   ``segment_reduce``).  Duplicate keys within a block all deposit in the
   same round, so no pre-combine (``unique_combine``) is ever needed;
4. losers (slot taken by a different key) continue to round ``r+1``.

The probe loop is a ``while_loop`` with an all-placed early exit: duplicate-
heavy streams (word counts) finish most blocks in one or two rounds
regardless of the configured ``max_probes``.  Lanes still unplaced after
``max_probes`` rounds are *counted* into the overflow output, never silently
dropped.  An existing table can be passed as ``init`` — the kernel then
*merges* into it (the post-shuffle use), bit-compatible with
``hashmap_insert``'s probe sequence, so eager- and kernel-built tables place
keys identically.

``choose_table_cap`` autotunes (capacity, block size, probe depth) under a
VMEM budget; ``interpret=None`` resolves via ``pallas_interpret_default`` —
interpret exactly when the backend is not a TPU — so CPU CI runs the exact
kernel program TPUs run.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels.segment_reduce import (
    _acc_dtype,
    _identity,
    _use_matmul,
    lane_fold,
    onehot_matmul,
    pallas_interpret_default,
    segment_reduce_lanes,
    select_scatter,
)

REDUCERS = ("sum", "prod", "min", "max")

# "slot free" sentinel — MUST match repro.core.containers.EMPTY_KEY (importing
# it would be cyclic: containers → reducers → kernels).  Asserted equal in
# tests/test_hash_kernel.py.
EMPTY_KEY = np.iinfo(np.int32).min

# The (capacity, block, probe-depth) tuner arithmetic is shared with the
# dense tuner and the measured autotuner in repro.core.cost; the delegates
# import lazily at call time (a module-level import would re-enter
# repro.core.__init__ mid-import — same constraint as segment_reduce).


def choose_probe_depth(n: int, table_cap: int) -> int:
    """Probe rounds for ``n`` pairs into a ``table_cap`` table (load-factor
    tiers; see ``cost.choose_probe_depth``)."""
    from repro.core.cost import choose_probe_depth as f

    return f(n, table_cap)


def choose_table_cap(
    n: int,
    v: int,
    reducer: str = "sum",
    dtype=jnp.float32,
    *,
    distinct_hint: int | None = None,
    vmem_budget: int | None = None,
) -> tuple[int, int, int]:
    """(table_cap, block_n, max_probes) for a fresh-table combine of ``n``
    pairs — the pick over ``cost.hash_table_candidates`` (shared grid)."""
    from repro.core import cost

    return cost.choose_table_cap(
        n, v, reducer, dtype, distinct_hint=distinct_hint,
        vmem_budget=cost.VMEM_BUDGET if vmem_budget is None else vmem_budget,
    )


def hash32(x: jax.Array) -> jax.Array:
    """splitmix32 finaliser → uint32.  Kernel-side copy of
    ``containers.hash32`` — identical constants, so kernel- and eager-built
    tables agree on every slot."""
    x = x.astype(jnp.uint32)
    x = (x ^ (x >> 16)) * jnp.uint32(0x7FEB352D)
    x = (x ^ (x >> 15)) * jnp.uint32(0x846CA68B)
    return x ^ (x >> 16)


def _srl(x, n: int):
    return jax.lax.shift_right_logical(x, jnp.int32(n))


def home_slot(keys: jax.Array, cap: int) -> jax.Array:
    """``hash32(keys) % cap`` in int32 arithmetic only (the kernel has no
    unsigned vectors): the same splitmix32 bits, wrapped multiplies, and an
    unsigned modulus rebuilt from a signed one on ``bits >> 1``."""
    x = keys.astype(jnp.int32)
    x = (x ^ _srl(x, 16)) * jnp.int32(0x7FEB352D)
    x = (x ^ _srl(x, 15)) * jnp.int32(0x846CA68B - (1 << 32))
    x = x ^ _srl(x, 16)
    if cap & (cap - 1) == 0:
        return x & (cap - 1)
    return (2 * (_srl(x, 1) % cap) + (x & 1)) % cap


def _hash_kernel(
    keys_ref, vals_ref, ikeys_ref, ivals_ref, iovf_ref,
    okeys_ref, ovals_ref, oovf_ref, *, cap, probes, reducer,
):
    step = pl.program_id(0)
    acc_dtype = ovals_ref.dtype

    @pl.when(step == 0)
    def _init():
        okeys_ref[...] = ikeys_ref[...]
        ovals_ref[...] = ivals_ref[...]
        oovf_ref[...] = iovf_ref[...]

    keys = keys_ref[...]  # [1, bn] int32; EMPTY_KEY marks a dead lane
    vals = vals_ref[...]  # [V, bn] acc dtype
    v, bn = vals.shape
    ident = _identity(reducer, acc_dtype)
    matmul = _use_matmul(reducer, acc_dtype)
    if matmul:
        # Zero dead-lane values up front: an all-zero one-hot column still
        # contracts 0·NaN = NaN into every slot (same hazard as the dense
        # kernel).
        vals = jnp.where(jnp.broadcast_to(keys, (v, bn)) != EMPTY_KEY, vals, 0)
    iota_c = jax.lax.broadcasted_iota(jnp.int32, (cap, bn), 0)

    def slot_keys(onehot):
        # tkeys[slot] for every lane, without dynamic indexing: a masked max
        # down the table axis (EMPTY_KEY = int32 min is the floor).
        tkeys = jnp.broadcast_to(okeys_ref[...], (cap, bn))
        return jnp.max(jnp.where(onehot, tkeys, EMPTY_KEY), axis=0,
                       keepdims=True)  # [1, bn]

    def probe_round(carry):
        r, slot, active = carry  # active [1, bn] int32: 1 = still unplaced
        onehot = slot == iota_c  # [C, bn]

        # Claim free slots: winner per slot = max key among claimants —
        # deterministic, and the same tie-break hashmap_insert uses.
        want = (active != 0) & (slot_keys(onehot) == EMPTY_KEY)
        claim = lane_fold(
            jnp.where(onehot, jnp.where(want, keys, EMPTY_KEY), EMPTY_KEY),
            "max",
        )  # [C, 1]
        tkeys = okeys_ref[...]
        okeys_ref[...] = jnp.where(tkeys == EMPTY_KEY, claim, tkeys)

        # Deposit where our key is now resident at our slot.  Duplicate keys
        # in the block all match the same row and are folded together by the
        # monoid — the kernel subsumes unique_combine.
        deposit = (active != 0) & (slot_keys(onehot) == keys)
        match = jnp.where(deposit, slot, -1) == iota_c  # [C, bn]
        if matmul:
            ovals_ref[...] += onehot_matmul(match, vals)
        else:
            ovals_ref[...] = select_scatter(
                ovals_ref[...], match, vals, reducer, ident
            )
        slot = jnp.where(slot + 1 == cap, 0, slot + 1)
        return r + 1, slot, jnp.where(deposit, 0, active)

    def keep_probing(carry):
        r, _, active = carry
        return (r < probes) & (lane_fold(active, "max")[0, 0] > 0)

    active0 = jnp.where(keys != EMPTY_KEY, 1, 0)
    _, _, active = jax.lax.while_loop(
        keep_probing, probe_round,
        (jnp.int32(0), home_slot(keys, cap), active0),
    )
    oovf_ref[...] += lane_fold(active, "sum")


@functools.partial(
    jax.jit,
    static_argnames=(
        "table_cap", "reducer", "max_probes", "block_n", "interpret"
    ),
)
def hash_aggregate(
    keys: jax.Array,  # [N] int32; lanes with key == EMPTY_KEY are dead
    vals: jax.Array,  # [N, V]
    table_cap: int,
    *,
    reducer: str = "sum",
    init: tuple[jax.Array, jax.Array, jax.Array] | None = None,
    max_probes: int | None = None,
    block_n: int | None = None,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Reduce-by-key into an open-addressing table; duplicates welcome.

    Returns ``(tkeys [C] int32, tvals [C, V] acc-dtype, overflow [] int32)``
    — free slots hold ``EMPTY_KEY`` / the reducer identity; ``overflow``
    counts lanes that exhausted ``max_probes`` (plus whatever ``init``
    carried).  ``init=(keys, vals, overflow)`` merges into an existing table
    with the same probe sequence as ``containers.hashmap_insert``.

    The pair stream enters lane-dense (keys ``[1, N]``, values ``[V, N]``)
    and the table sits down the sublanes (keys ``[C, 1]``, values
    ``[C, V]``), so each probe round works on ``[C, bn]`` tiles.
    """
    if reducer not in REDUCERS:
        raise ValueError(f"unknown reducer {reducer!r}; supported: {REDUCERS}")
    n = keys.shape[0]
    v = vals.shape[1]
    acc = _acc_dtype(vals.dtype)
    if init is None:
        ikeys = jnp.full((table_cap,), EMPTY_KEY, jnp.int32)
        ivals = jnp.full((table_cap, v), _identity(reducer, acc), acc)
        iovf = jnp.zeros((), jnp.int32)
    else:
        ikeys, ivals, iovf = init
        ikeys = ikeys.astype(jnp.int32)
        ivals = ivals.astype(acc)
    if n == 0:
        return ikeys, ivals, iovf.astype(jnp.int32)
    if interpret is None:
        interpret = pallas_interpret_default()
    if max_probes is None:
        max_probes = choose_probe_depth(n, table_cap)
    bn, n_pad = hash_aggregate_lanes(
        n, table_cap, v, reducer, vals.dtype, block_n=block_n
    )
    keys_p = jnp.pad(
        keys.astype(jnp.int32), (0, n_pad - n), constant_values=EMPTY_KEY
    )
    vals_t = jnp.pad(vals.astype(acc).T, ((0, 0), (0, n_pad - n)))

    kernel = functools.partial(
        _hash_kernel, cap=table_cap, probes=max_probes, reducer=reducer
    )
    table = lambda i: (0, 0)  # noqa: E731 — the resident table's block
    tkeys, tvals, ovf = pl.pallas_call(
        kernel,
        grid=(n_pad // bn,),
        in_specs=[
            pl.BlockSpec((1, bn), lambda i: (0, i)),
            pl.BlockSpec((v, bn), lambda i: (0, i)),
            pl.BlockSpec((table_cap, 1), table),
            pl.BlockSpec((table_cap, v), table),
            pl.BlockSpec((1, 1), table),
        ],
        out_specs=(
            pl.BlockSpec((table_cap, 1), table),
            pl.BlockSpec((table_cap, v), table),
            pl.BlockSpec((1, 1), table),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((table_cap, 1), jnp.int32),
            jax.ShapeDtypeStruct((table_cap, v), acc),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
        ),
        interpret=interpret,
    )(
        keys_p[None, :], vals_t, ikeys[:, None], ivals,
        iovf.astype(jnp.int32).reshape(1, 1),
    )
    return tkeys[:, 0], tvals, ovf[0, 0]


def hash_aggregate_lanes(
    n: int, table_cap: int, v: int, reducer: str = "sum", dtype=jnp.float32,
    block_n: int | None = None,
) -> tuple[int, int]:
    """(block_n, padded lane count) one ``hash_aggregate`` pass processes for
    ``n`` pairs into a ``table_cap`` table — the static half of the
    hash-kernel occupancy accounting."""
    if block_n is None:
        from repro.core.cost import hash_block_n

        block_n = hash_block_n(table_cap, n, v, reducer, dtype)
    return segment_reduce_lanes(n, table_cap, v, reducer, dtype, block_n)
