"""Per-kernel validation: Pallas (interpret=True) and chunked-jnp paths vs
the pure-jnp oracles, swept over shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels import ref as R
from repro.kernels.flash_attention import flash_attention
from repro.kernels.kmeans_assign import kmeans_assign
from repro.kernels.segment_reduce import segment_reduce

rng = np.random.RandomState(0)


def t(shape, dtype=np.float32, scale=0.5):
    return jnp.asarray(rng.randn(*shape).astype(dtype) * scale)


ATTN_CASES = [
    # B, Hq, Hkv, Sq, Skv, D, causal, window, softcap
    (2, 4, 2, 64, 64, 32, True, None, 0.0),
    (1, 8, 8, 128, 128, 64, True, None, 0.0),
    (2, 4, 4, 96, 96, 32, True, 32, 0.0),
    (1, 4, 2, 64, 64, 32, False, None, 0.0),
    (1, 4, 2, 64, 64, 32, True, None, 20.0),
    (2, 8, 2, 1, 256, 64, True, None, 0.0),  # decode
    (1, 4, 4, 7, 133, 32, True, None, 0.0),  # ragged
    (1, 2, 1, 33, 65, 16, True, 16, 5.0),  # window + softcap + ragged
]


@pytest.mark.parametrize("case", ATTN_CASES)
def test_flash_attention_vs_ref(case):
    b, hq, hkv, sq, skv, d, causal, window, cap = case
    q, k, v = t((b, hq, sq, d)), t((b, hkv, skv, d)), t((b, hkv, skv, d))
    out = flash_attention(
        q, k, v, causal=causal, window=window, softcap=cap,
        block_q=32, block_k=32,
    )
    ref = R.attention_ref(q, k, v, causal=causal, window=window, softcap=cap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)


@pytest.mark.parametrize("case", ATTN_CASES)
def test_chunked_attention_vs_ref(case):
    b, hq, hkv, sq, skv, d, causal, window, cap = case
    q, k, v = t((b, hq, sq, d)), t((b, hkv, skv, d)), t((b, hkv, skv, d))
    out = ops.attention_chunked(
        q, k, v, causal=causal, window=window, softcap=cap,
        block_q=32, block_k=32,
    )
    ref = R.attention_ref(q, k, v, causal=causal, window=window, softcap=cap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)


def test_flash_attention_bf16():
    q = t((1, 4, 64, 32)).astype(jnp.bfloat16)
    k = t((1, 2, 64, 32)).astype(jnp.bfloat16)
    v = t((1, 2, 64, 32)).astype(jnp.bfloat16)
    out = flash_attention(q, k, v, block_q=32, block_k=32)
    ref = R.attention_ref(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32)
    )
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref), atol=2e-2
    )


def test_chunked_attention_grad_finite():
    q, k, v = t((1, 2, 32, 16)), t((1, 2, 32, 16)), t((1, 2, 32, 16))

    def f(q):
        return jnp.sum(ops.attention_chunked(q, k, v, block_q=16, block_k=16))

    g = jax.grad(f)(q)
    assert bool(jnp.isfinite(g).all())


@pytest.mark.parametrize(
    "n,v,k,bn", [(1000, 4, 8, 256), (37, 1, 3, 16), (4096, 16, 64, 512),
                 (100, 3, 1, 100)]
)
def test_segment_reduce_vs_ref(n, v, k, bn):
    ids = jnp.asarray(rng.randint(-1, k, n).astype(np.int32))
    vals = t((n, v))
    out = segment_reduce(ids, vals, k, block_n=bn)
    ref = R.segment_reduce_ref(ids, vals, k)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


# -- generalized (monoid) segment-reduce ---------------------------------------


def _np_segment(ids, vals, k, reducer):
    fn = {"sum": np.add, "prod": np.multiply, "min": np.minimum,
          "max": np.maximum}[reducer]
    if np.issubdtype(vals.dtype, np.floating):
        ident = {"sum": 0.0, "prod": 1.0, "min": np.inf, "max": -np.inf}[reducer]
        acc = np.float64
    else:
        ident = {"sum": 0, "prod": 1, "min": np.iinfo(np.int32).max,
                 "max": np.iinfo(np.int32).min}[reducer]
        acc = np.int64
    out = np.full((k,) + vals.shape[1:], ident, acc)
    for i, s in enumerate(np.asarray(ids)):
        if 0 <= s < k:
            out[s] = fn(out[s], np.asarray(vals[i], acc))
    return out


@pytest.mark.parametrize("reducer", ["sum", "prod", "min", "max"])
@pytest.mark.parametrize(
    "n,v,k,bn",
    [
        (1000, 4, 8, 256),   # pair count not a multiple of the block
        (1023, 2, 13, 128),  # K not a multiple of 8
        (77, 3, 127, 16),    # K not a multiple of 8 or 128
        (513, 1, 129, 64),   # K just past a lane boundary
    ],
)
def test_segment_reduce_monoid_vs_numpy(reducer, n, v, k, bn):
    ids = jnp.asarray(rng.randint(-2, k + 2, n).astype(np.int32))
    if reducer == "prod":
        vals = jnp.asarray(
            rng.choice([1.0, -1.0, 0.5, 2.0], (n, v)).astype(np.float32)
        )
    else:
        vals = t((n, v))
    out = segment_reduce(ids, vals, k, reducer=reducer, block_n=bn)
    ref = _np_segment(np.asarray(ids), np.asarray(vals), k, reducer)
    np.testing.assert_allclose(
        np.asarray(out, np.float64), ref, rtol=2e-4, atol=1e-4
    )


@pytest.mark.parametrize("reducer", ["sum", "min", "max", "prod"])
def test_segment_reduce_int32_exact(reducer):
    n, v, k = 333, 2, 11
    ids = jnp.asarray(rng.randint(-1, k + 1, n).astype(np.int32))
    if reducer == "prod":
        vals = jnp.asarray(rng.choice([1, -1, 2], (n, v)).astype(np.int32))
    else:
        vals = jnp.asarray(rng.randint(-50, 50, (n, v)).astype(np.int32))
    out = segment_reduce(ids, vals, k, reducer=reducer)
    assert out.dtype == jnp.int32
    ref = _np_segment(np.asarray(ids), np.asarray(vals), k, reducer)
    np.testing.assert_array_equal(np.asarray(out, np.int64), ref)


@pytest.mark.parametrize("n", [1, 7, 255, 1025])
def test_segment_reduce_interpret_equals_segment_sum(n):
    """Interpret-mode kernel ≡ jax.ops.segment_sum on the same drop mask."""
    k = 9
    ids = jnp.asarray(rng.randint(-1, k, n).astype(np.int32))
    vals = t((n, 3))
    out = segment_reduce(ids, vals, k, interpret=True)
    safe = jnp.where(ids >= 0, ids, k)
    want = jax.ops.segment_sum(vals, safe, num_segments=k + 1)[:k]
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-4)


def test_segment_reduce_autotune_and_lanes():
    from repro.kernels.segment_reduce import (
        choose_block_n,
        segment_reduce_lanes,
    )

    # tiny working sets → max block; huge K → one lane tile; floor respected
    assert choose_block_n(100_000, 8, 4) == 2048
    assert choose_block_n(100_000, 20_000, 1, "sum", np.int32) == 128
    assert choose_block_n(5, 8, 4) == 128
    bn, lanes = segment_reduce_lanes(1000, 8, 4)
    assert lanes % bn == 0 and lanes >= 1000
    # autotuned call agrees with the oracle
    ids = jnp.asarray(rng.randint(0, 8, 1000).astype(np.int32))
    vals = t((1000, 4))
    out = segment_reduce(ids, vals, 8)  # block_n=None → choose_block_n
    ref = R.segment_reduce_ref(ids, vals, 8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


# (reducer, value dtype, integer-valued inputs): a float sum on the MXU
# one-hot matmul, a float max on the VPU select fold, an exact int32 sum.
# Integer-valued float sums are exact in any order, so every block size
# must give the same bits.
_GRID_SETTINGS = {
    "f32-sum-mxu": ("sum", np.float32, True),
    "f32-max-vpu": ("max", np.float32, False),
    "i32-sum": ("sum", np.int32, True),
}
_GRID_N, _GRID_K, _GRID_V = 2048, 16, 4


def _segment_grid_cases():
    from repro.core import cost

    return [
        pytest.param(name, bn, id=f"{name}-bn{bn}")
        for name, (red, dt, _) in _GRID_SETTINGS.items()
        for bn, _ws in cost.segment_block_candidates(
            _GRID_N, _GRID_K, _GRID_V, red, dt
        )
    ]


@pytest.mark.parametrize("setting,block_n", _segment_grid_cases())
def test_segment_reduce_same_bits_at_every_grid_block(setting, block_n):
    """The kernel's result does not depend on its launch geometry: every
    ``block_n`` of ``cost.segment_block_candidates`` gives the NumPy
    oracle's bits (ids outside ``[0, K)`` dropped)."""
    reducer, dtype, integral = _GRID_SETTINGS[setting]
    r = np.random.RandomState(7)
    ids = r.randint(-2, _GRID_K + 2, _GRID_N).astype(np.int32)
    if integral:
        vals = r.randint(-50, 51, (_GRID_N, _GRID_V)).astype(dtype)
    else:
        vals = r.randn(_GRID_N, _GRID_V).astype(dtype)
    got = segment_reduce(
        jnp.asarray(ids), jnp.asarray(vals), _GRID_K, reducer=reducer,
        block_n=block_n,
    )
    ufunc = {"sum": np.add, "max": np.maximum}[reducer]
    ident = {"sum": 0, "max": -np.inf}[reducer]
    want = np.full((_GRID_K, _GRID_V), ident, got.dtype)
    keep = (ids >= 0) & (ids < _GRID_K)
    ufunc.at(want, ids[keep], vals[keep].astype(got.dtype))
    np.testing.assert_array_equal(np.asarray(got), want)


def test_segment_reduce_nan_in_dropped_lane_stays_out():
    """A non-finite value on a dropped lane (id<0 / id>=K) must not leak:
    0·NaN = NaN through the one-hot matmul unless the lane is zeroed."""
    ids = jnp.asarray(np.array([0, -1, 9], np.int32))  # -1 dropped, 9 >= K
    vals = jnp.asarray(np.array([[1.0], [np.nan], [np.inf]], np.float32))
    out = segment_reduce(ids, vals, 2, reducer="sum")
    np.testing.assert_array_equal(np.asarray(out), [[1.0], [0.0]])


def test_segment_reduce_empty_stream_returns_identity():
    for reducer, ident in [("sum", 0.0), ("prod", 1.0), ("min", np.inf),
                           ("max", -np.inf)]:
        out = segment_reduce(
            jnp.zeros((0,), jnp.int32), jnp.zeros((0, 3), jnp.float32), 4,
            reducer=reducer,
        )
        assert out.shape == (4, 3)
        np.testing.assert_array_equal(np.asarray(out), np.full((4, 3), ident))


def test_segment_reduce_rejects_unknown_reducer():
    ids = jnp.zeros((4,), jnp.int32)
    vals = jnp.zeros((4, 1), jnp.float32)
    with pytest.raises(ValueError, match="unknown reducer"):
        segment_reduce(ids, vals, 2, reducer="mean")


@pytest.mark.parametrize("n,d,k,bn", [(1000, 3, 5, 256), (777, 8, 13, 128),
                                      (64, 2, 2, 64)])
def test_kmeans_assign_vs_ref(n, d, k, bn):
    pts = t((n, d))
    ctr = t((k, d))
    a, stats = kmeans_assign(pts, ctr, block_n=bn)
    a_ref, stats_ref = R.kmeans_assign_ref(pts, ctr)
    assert bool(jnp.all(a == a_ref))
    np.testing.assert_allclose(np.asarray(stats), np.asarray(stats_ref), atol=1e-3)


@pytest.mark.parametrize("chunk", [8, 16, 64])
@pytest.mark.parametrize("with_init", [False, True])
def test_ssd_chunked_vs_ref(chunk, with_init):
    B, S, H, P, G, N = 2, 100, 4, 8, 2, 16
    x = t((B, S, H, P))
    dt = jnp.abs(t((B, S, H), scale=0.3)) + 0.01
    a = -jnp.abs(t((H,), scale=2.0)) - 0.1
    b = t((B, S, G, N))
    c = t((B, S, G, N))
    h0 = t((B, H, P, N)) if with_init else None
    y1, hT1 = ops.ssd_chunked(x, dt, a, b, c, chunk=chunk, init_state=h0)
    y2, hT2 = R.ssd_ref(x, dt, a, b, c, init_state=h0)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=2e-5)
    np.testing.assert_allclose(np.asarray(hT1), np.asarray(hT2), atol=2e-5)


def test_ssd_extreme_decay_no_nan():
    """The inf·0 upper-triangle hazard (regression for the zamba2 NaN)."""
    B, S, H, P, G, N = 1, 64, 2, 4, 1, 8
    x = t((B, S, H, P))
    dt = jnp.abs(t((B, S, H), scale=2.0)) + 1.0  # large steps
    a = jnp.asarray([-16.0, -8.0])
    b, c = t((B, S, G, N)), t((B, S, G, N))
    y, hT = ops.ssd_chunked(x, dt, a, b, c, chunk=16)
    assert bool(jnp.isfinite(y).all()) and bool(jnp.isfinite(hT).all())


@pytest.mark.parametrize("chunk", [8, 16, 32])
@pytest.mark.parametrize("with_init", [False, True])
def test_rwkv6_chunked_vs_ref(chunk, with_init):
    B, S, H, K, V = 2, 70, 2, 8, 8
    r, k, v = t((B, S, H, K)), t((B, S, H, K)), t((B, S, H, V))
    w = jax.nn.sigmoid(t((B, S, H, K))) * 0.8 + 0.15
    u = t((H, K))
    s0 = t((B, H, K, V)) if with_init else None
    y1, sT1 = ops.rwkv6_chunked(r, k, v, w, u, chunk=chunk, init_state=s0)
    y2, sT2 = R.rwkv6_ref(r, k, v, w, u, init_state=s0)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=5e-5)
    np.testing.assert_allclose(np.asarray(sT1), np.asarray(sT2), atol=5e-5)


def test_decode_chaining_equals_full_scan():
    """prefill-chunk + per-token decode == one full pass (SSD + RWKV)."""
    B, S, H, P, G, N = 1, 48, 2, 4, 1, 8
    x = t((B, S, H, P))
    dt = jnp.abs(t((B, S, H), scale=0.2)) + 0.01
    a = -jnp.abs(t((H,))) - 0.1
    b, c = t((B, S, G, N)), t((B, S, G, N))
    y_full, h_full = R.ssd_ref(x, dt, a, b, c)
    y1, h1 = ops.ssd_chunked(x[:, :32], dt[:, :32], a, b[:, :32], c[:, :32], chunk=16)
    ys = [y1]
    h = h1
    for i in range(32, S):
        yi, h = ops.ssd_chunked(
            x[:, i : i + 1], dt[:, i : i + 1], a, b[:, i : i + 1],
            c[:, i : i + 1], chunk=16, init_state=h,
        )
        ys.append(yi)
    y_chain = jnp.concatenate(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_chain), np.asarray(y_full), atol=3e-5)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_full), atol=3e-5)


@pytest.mark.parametrize("chunk", [16, 32])
def test_ssd_pallas_vs_ref(chunk):
    from repro.kernels.ssd_scan import ssd_scan

    B, S, H, P, G, N = 2, 96, 4, 8, 2, 16
    x = t((B, S, H, P))
    dt = jnp.abs(t((B, S, H), scale=0.3)) + 0.01
    a = -jnp.abs(t((H,), scale=2.0)) - 0.1
    b, c = t((B, S, G, N)), t((B, S, G, N))
    y1, h1 = ssd_scan(x, dt, a, b, c, chunk=chunk)
    y2, h2 = R.ssd_ref(x, dt, a, b, c)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=3e-5)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), atol=3e-5)


@pytest.mark.parametrize("chunk", [16, 32])
def test_rwkv6_pallas_vs_ref(chunk):
    from repro.kernels.rwkv6_scan import rwkv6_scan

    B, S, H, K, V = 2, 64, 2, 8, 8
    r, k, v = t((B, S, H, K)), t((B, S, H, K)), t((B, S, H, V))
    w = jax.nn.sigmoid(t((B, S, H, K))) * 0.8 + 0.15
    u = t((H, K))
    y1, s1 = rwkv6_scan(r, k, v, w, u, chunk=chunk)
    y2, s2 = R.rwkv6_ref(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=5e-5)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=5e-5)
