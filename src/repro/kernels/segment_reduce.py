"""Pallas segment-reduce: the eager-reduction combiner as a TPU kernel.

Reduces a stream of (id, value-row) pairs into a dense ``[K, V]`` accumulator
that lives in VMEM for the whole pass — the TPU shape of the paper's
*thread-local cache for a small fixed key range* (§2.3.3), generalized from
``sum`` to the full ``Reducer`` monoid surface (sum / min / max / prod).

The pair stream is fed **lane-dense**: ids as a ``[1, N]`` row and values
transposed to ``[V, N]``, so the pair axis sits on the 128 vector lanes.  A
narrow ``[N, V]`` operand would be padded to 128 lanes in HBM and VMEM; the
transposed one is the layout XLA already gives a narrow array, so the
transpose costs nothing.  Per block of ``bn`` pairs the key axis runs down
the sublanes:

* **one-hot matmul** (float sum): the scatter-add is a one-hot matmul, so the
  MXU does the reduction:

      onehot[K, bn] = (ids == iota_K)   →   acc += onehot @ valsᵀ

* **select-scatter** (min / max / prod, and integer sum, which must stay
  exact): one value column at a time, select each lane into its key's row
  (identity elsewhere) and fold the lane axis on the VPU:

      masked[K, bn] = where(onehot, vals[c], identity)  →  acc[:, c] = op(acc[:, c], fold(masked))

Grid iterates over pair-blocks (sequential on TPU); the output BlockSpec maps
every step to the same ``[K, V]`` tile, so the accumulator never leaves VMEM
between steps.  Negative ids and ids ``>= K`` never match the iota and are
dropped.  Blocks are whole multiples of the 128 lanes; ``choose_block_n``
picks the block size against a VMEM budget per strategy.
``interpret=None`` resolves via ``pallas_interpret_default()`` — interpret
mode exactly when the backend is not a TPU — so CPU runs exercise the same
kernel program the chip compiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

REDUCERS = ("sum", "prod", "min", "max")

# Vector lanes per vreg: every pair block is a whole number of lane tiles.
LANES = 128

# The VMEM-budget/candidate-scoring arithmetic lives in repro.core.cost
# (shared with the hash-combine kernel's sizing).  The
# delegates below import it lazily at call time: a module-level import would
# re-enter repro.core.__init__ while this module is itself being imported by
# the containers → reducers → kernels chain.


def _acc_dtype(dtype):
    """Accumulator dtype: f32 for floats (bf16 upcast), i32 for ints."""
    from repro.core.cost import acc_dtype

    return acc_dtype(dtype)


def _use_matmul(reducer: str, acc_dtype) -> bool:
    from repro.core.cost import use_matmul

    return use_matmul(reducer, acc_dtype)


def lane_block(block_n: int) -> int:
    """``block_n`` rounded up to whole 128-lane tiles (the kernels' block)."""
    return max(LANES, -(-block_n // LANES) * LANES)


def choose_block_n(
    n: int, num_segments: int, v: int, reducer: str = "sum",
    dtype=jnp.float32, vmem_budget: int | None = None,
) -> int:
    """Largest power-of-two block (128..2048) whose per-step working set fits
    — the pick over ``cost.segment_block_candidates`` (shared grid)."""
    from repro.core import cost

    return cost.choose_block_n(
        n, num_segments, v, reducer, dtype,
        cost.VMEM_BUDGET if vmem_budget is None else vmem_budget,
    )


def pallas_interpret_default() -> bool:
    """Run kernels in interpret mode?  Exactly when the backend is not a TPU:
    on the chip every kernel is compiled by Mosaic."""
    return jax.default_backend() != "tpu"


def _identity(reducer: str, dtype):
    dtype = jnp.dtype(dtype)
    if reducer == "sum":
        return jnp.asarray(0, dtype)
    if reducer == "prod":
        return jnp.asarray(1, dtype)
    lo, hi = (
        (-jnp.inf, jnp.inf)
        if jnp.issubdtype(dtype, jnp.floating)
        else (jnp.iinfo(dtype).min, jnp.iinfo(dtype).max)
    )
    return jnp.asarray(hi if reducer == "min" else lo, dtype)


def _combine(reducer: str):
    return {
        "sum": jnp.add,
        "prod": jnp.multiply,
        "min": jnp.minimum,
        "max": jnp.maximum,
    }[reducer]


def lane_fold(x, reducer: str):
    """Fold ``x [R, W]`` along its lanes with the reducer → ``[R, 1]``.

    ``W`` is a multiple of 128.  Whole lane tiles fold with aligned slices;
    the last tile folds by a rotate-and-combine butterfly, after which every
    lane holds the total.  Works for every monoid and dtype alike (Mosaic
    has no multiplicative lane reduction).
    """
    op = _combine(reducer)
    acc = x[:, :LANES]
    for t in range(1, x.shape[1] // LANES):
        acc = op(acc, x[:, t * LANES:(t + 1) * LANES])
    x = acc
    shift = LANES // 2
    while shift:
        x = op(x, pltpu.roll(x, shift, 1))
        shift //= 2
    return x[:, :1]


def select_scatter(acc, onehot, vals, reducer: str, ident):
    """``acc [R, V]`` ⊕= per-row fold of ``vals [V, bn]`` over the lanes whose
    ``onehot [R, bn]`` is set — one value column at a time, so no
    ``[R, bn, V]`` intermediate ever exists."""
    r, v = acc.shape
    col = jax.lax.broadcasted_iota(jnp.int32, (r, v), 1)
    op = _combine(reducer)
    for c in range(v):
        part = lane_fold(jnp.where(onehot, vals[c:c + 1, :], ident), reducer)
        acc = jnp.where(col == c, op(acc, part), acc)
    return acc


def onehot_matmul(onehot, vals):
    """``onehot [R, bn]`` @ ``vals [V, bn]``ᵀ → ``[R, V]`` in f32 on the MXU
    (full f32 precision: the one-hot side is exact, the values must be)."""
    return jax.lax.dot_general(
        onehot.astype(jnp.float32), vals,
        (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def _segment_reduce_kernel(ids_ref, vals_ref, out_ref, *, k, reducer):
    i = pl.program_id(0)
    acc_dtype = out_ref.dtype
    ident = _identity(reducer, acc_dtype)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.full(out_ref.shape, ident, acc_dtype)

    ids = ids_ref[...]  # [1, bn] int32
    vals = vals_ref[...]  # [V, bn] acc dtype
    v, bn = vals.shape
    onehot = ids == jax.lax.broadcasted_iota(jnp.int32, (k, bn), 0)  # [K, bn]
    if _use_matmul(reducer, acc_dtype):
        # Zero the values of dropped lanes, not just their one-hot columns: an
        # all-zero onehot column still contracts 0·NaN = NaN into every key.
        idv = jnp.broadcast_to(ids, (v, bn))
        vals = jnp.where((idv >= 0) & (idv < k), vals, 0)
        out_ref[...] += onehot_matmul(onehot, vals)
    else:
        out_ref[...] = select_scatter(out_ref[...], onehot, vals, reducer, ident)


@functools.partial(
    jax.jit, static_argnames=("num_segments", "reducer", "block_n", "interpret")
)
def segment_reduce(
    ids: jax.Array,  # [N] int32; ids outside [0, num_segments) are dropped
    vals: jax.Array,  # [N, V]
    num_segments: int,
    *,
    reducer: str = "sum",
    block_n: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Dense ``[K, V]`` reduce-by-key; returns the accumulator dtype
    (f32 for float inputs, i32 for ints)."""
    if reducer not in REDUCERS:
        raise ValueError(f"unknown reducer {reducer!r}; supported: {REDUCERS}")
    n, v = vals.shape
    acc = _acc_dtype(vals.dtype)
    if n == 0:  # empty pair stream → the identity accumulator
        return jnp.full((num_segments, v), _identity(reducer, acc), acc)
    if interpret is None:
        interpret = pallas_interpret_default()
    bn, n_pad = segment_reduce_lanes(
        n, num_segments, v, reducer, vals.dtype, block_n=block_n
    )
    ids_p = jnp.pad(ids.astype(jnp.int32), (0, n_pad - n), constant_values=-1)
    vals_t = jnp.pad(vals.astype(acc).T, ((0, 0), (0, n_pad - n)))

    kernel = functools.partial(
        _segment_reduce_kernel, k=num_segments, reducer=reducer
    )
    return pl.pallas_call(
        kernel,
        grid=(n_pad // bn,),
        in_specs=[
            pl.BlockSpec((1, bn), lambda i: (0, i)),
            pl.BlockSpec((v, bn), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((num_segments, v), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((num_segments, v), acc),
        interpret=interpret,
    )(ids_p[None, :], vals_t)


def segment_reduce_lanes(n: int, num_segments: int, v: int,
                         reducer: str = "sum", dtype=jnp.float32,
                         block_n: int | None = None) -> tuple[int, int]:
    """(block_n, padded lane count) the kernel will process for ``n`` pairs —
    the static half of the occupancy accounting in ``MapReduceStats``."""
    if block_n is None:
        block_n = choose_block_n(n, num_segments, v, reducer, dtype)
    bn = min(lane_block(block_n), lane_block(max(n, 1)))
    return bn, -(-max(n, 1) // bn) * bn
