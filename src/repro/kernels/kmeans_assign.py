"""Pallas fused k-means assignment + statistics (the paper's k-means hot loop).

One pass over a point tile does everything the assignment step needs:

    d²  = ‖x‖² − 2 x·cᵀ + ‖c‖²      (MXU matmul; the ‖x‖² term is dropped —
                                      it does not change the argmin)
    a   = argmin_k d²                 (VPU)
    acc[K, D+1] += onehotᵀ @ [x | 1]  (MXU; eager reduction)

so the per-cluster Σx and counts — the entire MapReduce payload — accumulate
in a VMEM-resident ``[K, D+1]`` tile across the sequential grid, and the
points are read from HBM exactly once.  This is the kernel-level form of the
paper's eager reduction: emit→reduce fused into the map body.  The scatter
itself is ``onehot_accumulate`` — a one-hot-matmul accumulator, the pattern
the generalized segment-reduce kernel also uses — applied to points with a
ones column appended, so Σx and the counts come out of a single matmul.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def onehot_accumulate(ids, vals, k: int, *, valid=None, acc_dtype=jnp.float32):
    """One-hot-matmul scatter-add: ``[bn]`` ids × ``[bn, V]`` vals → ``[K, V]``.

    Lanes with ``ids`` outside ``[0, k)`` (or ``valid == False``) contribute
    nothing.
    """
    bn = ids.shape[0]
    iota_k = jax.lax.broadcasted_iota(jnp.int32, (bn, k), 1)
    onehot = ids[:, None] == iota_k  # [bn, K]
    if valid is not None:
        onehot &= valid[:, None]
    return jax.lax.dot_general(
        onehot.astype(acc_dtype), vals.astype(acc_dtype),
        (((0,), (0,)), ((), ())),
        preferred_element_type=acc_dtype,
    )  # [K, V]


def _kmeans_kernel(pts_ref, ctr_ref, assign_ref, stats_ref, *, k, bn, n_true):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        stats_ref[...] = jnp.zeros_like(stats_ref)

    x = pts_ref[...].astype(jnp.float32)  # [bn, D]
    c = ctr_ref[...].astype(jnp.float32)  # [K, D]
    # −2 x·cᵀ + ‖c‖²  (argmin-equivalent distance)
    xc = jax.lax.dot_general(
        x, c, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # [bn, K]
    d2 = jnp.sum(c * c, axis=1)[None, :] - 2.0 * xc
    assign = jnp.argmin(d2, axis=1).astype(jnp.int32)  # [bn]

    row = i * bn + jax.lax.broadcasted_iota(jnp.int32, (bn,), 0)
    valid = row < n_true
    assign_ref[...] = jnp.where(valid, assign, -1)

    x1 = jnp.concatenate([x, jnp.ones((bn, 1), jnp.float32)], axis=1)
    stats_ref[...] += onehot_accumulate(assign, x1, k, valid=valid)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def kmeans_assign(
    points: jax.Array,  # [N, D]
    centers: jax.Array,  # [K, D]
    *,
    block_n: int = 1024,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Returns (assignments [N] int32, stats [K, D+1] = [Σx | count])."""
    from repro.kernels.segment_reduce import pallas_interpret_default

    if interpret is None:
        interpret = pallas_interpret_default()
    n, d = points.shape
    k = centers.shape[0]
    bn = min(block_n, n)
    n_pad = -(-n // bn) * bn
    pts_p = jnp.pad(points, ((0, n_pad - n), (0, 0)))

    kernel = functools.partial(_kmeans_kernel, k=k, bn=bn, n_true=n)
    assign, stats = pl.pallas_call(
        kernel,
        grid=(n_pad // bn,),
        in_specs=[
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((k, d), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn,), lambda i: (i,)),
            pl.BlockSpec((k, d + 1), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad,), jnp.int32),
            jax.ShapeDtypeStruct((k, d + 1), jnp.float32),
        ],
        interpret=interpret,
    )(pts_p, centers)
    return assign[:n], stats
