"""BlazeSession: compiled-executable reuse across iterations, cache-miss
triggers on config changes, and the JAX compat shim on the installed JAX."""
import jax.numpy as jnp
import numpy as np

from repro.core import (
    BlazeSession,
    DistRange,
    data_mesh,
    distribute,
    get_default_session,
    make_dist_hashmap,
    map_reduce,
)
from repro.core.algorithms import (
    gmm_em,
    gmm_em_reference,
    kmeans,
    kmeans_reference,
    pagerank,
    pagerank_reference,
)
from repro.data.synthetic import cluster_points, rmat_edges

import pytest


def _sq_mapper(v, emit):
    emit(v % 4, v * v)


def _first_col_mapper(i, x, emit):
    emit(i % 4, x[0])


def _tok_mapper(i, toks, emit):
    emit(toks, 1, mask=toks >= 0)


# -- compat shim ---------------------------------------------------------------


def test_compat_imports_on_installed_jax():
    # The compat names are plain aliases of the installed JAX 0.9 surface.
    import jax

    import repro.core  # noqa: F401
    from repro.compat import (
        AxisType,
        get_abstract_mesh,
        make_mesh,
        set_mesh,
        shard_map,
    )

    assert shard_map is jax.shard_map
    assert make_mesh is jax.make_mesh and set_mesh is jax.set_mesh
    assert AxisType is jax.sharding.AxisType
    assert get_abstract_mesh is jax.sharding.get_abstract_mesh


def test_compat_shard_map_accepts_either_check_flag():
    # JAX 0.9 spells the replication check ``check_vma``; either setting
    # (and the default) maps the same function.
    from jax.sharding import PartitionSpec as P

    from repro.compat import shard_map

    mesh = data_mesh()
    x = jnp.arange(8, dtype=jnp.float32)
    for kw in ({"check_vma": False}, {"check_vma": True}, {}):
        f = shard_map(
            lambda v: v * 2, mesh=mesh, in_specs=P("data"), out_specs=P("data"),
            **kw,
        )
        np.testing.assert_allclose(np.asarray(f(x)), np.arange(8.0) * 2)


def test_compat_make_mesh_and_set_mesh():
    from repro.compat import AxisType, make_mesh, set_mesh

    mesh = make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    assert mesh.axis_names == ("data",)
    with set_mesh(mesh):
        from repro.compat import get_abstract_mesh

        assert get_abstract_mesh().axis_names == ("data",)


# -- persistent compile cache --------------------------------------------------


@pytest.fixture
def restore_compile_cache():
    """Put JAX's persistent-cache settings back after a test changes them."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs")
    before = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in before.items():
        jax.config.update(n, v)
    compilation_cache.reset_cache()


def test_compile_cache_uses_env_dir(tmp_path, monkeypatch, restore_compile_cache):
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from repro.launch.compile_cache import enable_compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    # a compile now lands in that directory
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compilation_cache.reset_cache()
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()
    assert any(tmp_path.iterdir())


def test_compile_cache_default_is_checkout_dir(monkeypatch, restore_compile_cache):
    import os

    import jax

    from repro.launch import compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(root, ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want


# -- executable reuse ----------------------------------------------------------


def test_session_reuses_executable_across_iterations():
    sess = BlazeSession()
    for i in range(10):
        out, st = sess.map_reduce(
            DistRange(0, 64, 1), _sq_mapper, "sum", jnp.zeros((4,), jnp.int32),
            return_stats=True,
        )
        assert st.compiles == (1 if i == 0 else 0)
        assert st.cache_hits == (0 if i == 0 else 1)
    assert sess.stats.calls == 10
    assert sess.stats.compiles == 1
    assert sess.stats.cache_hits == 9
    info = sess.cache_info()
    assert info["entries"] == 1 and info["hit_rate"] == 0.9


def test_cache_miss_on_engine_wire_and_shape_change():
    sess = BlazeSession()
    pts = distribute(np.random.RandomState(0).randn(64, 2).astype(np.float32))
    t4 = jnp.zeros((4,), jnp.float32)
    sess.map_reduce(pts, _first_col_mapper, "sum", t4)  # compile 1
    sess.map_reduce(pts, _first_col_mapper, "sum", t4)  # hit
    sess.map_reduce(pts, _first_col_mapper, "sum", t4, engine="naive")  # 2
    sess.map_reduce(pts, _first_col_mapper, "sum", t4, wire="bf16")  # 3
    sess.map_reduce(  # 4: target shape change
        pts, _first_col_mapper, "sum", jnp.zeros((8,), jnp.float32)
    )
    assert sess.stats.compiles == 4
    assert sess.stats.cache_hits == 1


def test_sessions_have_isolated_caches():
    a, b = BlazeSession(), BlazeSession()
    t = jnp.zeros((4,), jnp.int32)
    a.map_reduce(DistRange(0, 32, 1), _sq_mapper, "sum", t)
    b.map_reduce(DistRange(0, 32, 1), _sq_mapper, "sum", t)
    assert a.stats.compiles == 1 and b.stats.compiles == 1
    assert a.stats.cache_hits == 0 and b.stats.cache_hits == 0


def test_hash_target_executable_reuse():
    sess = BlazeSession()
    lines = np.random.RandomState(0).randint(0, 50, (64, 8)).astype(np.int32)
    lv = distribute(lines, sess.mesh)
    for i in range(3):
        hm = make_dist_hashmap(sess.mesh, 256, (), jnp.int32, "sum")
        hm, st = sess.map_reduce(
            lv, _tok_mapper, "sum", hm, return_stats=True
        )
        assert st.compiles == (1 if i == 0 else 0)
    assert sess.stats.compiles == 1 and sess.stats.cache_hits == 2
    import collections

    ref = collections.Counter(lines.reshape(-1).tolist())
    assert {k: int(v) for k, v in hm.to_dict().items()} == dict(ref)


def test_default_session_backs_free_map_reduce():
    base = get_default_session().stats.compiles

    def m(v, emit):  # fresh function object → fresh cache key, isolated test
        emit(0, v)

    _, st1 = map_reduce(
        DistRange(0, 32, 1), m, "sum", jnp.zeros((1,), jnp.int32),
        return_stats=True,
    )
    _, st2 = map_reduce(
        DistRange(0, 32, 1), m, "sum", jnp.zeros((1,), jnp.int32),
        return_stats=True,
    )
    assert st1.compiles == 1 and st2.compiles == 0 and st2.cache_hits == 1
    assert get_default_session().stats.compiles == base + 1


# -- iterative drivers: N iterations, 1 compile per (engine, shape) config ----


def test_pagerank_10_iters_one_compile_per_config():
    sess = BlazeSession()
    edges = rmat_edges(6, 8, seed=3)  # 64 nodes
    res = pagerank(edges, 64, tol=0.0, max_iters=10, session=sess)
    assert res.iterations == 10
    # Exactly 3 configs per iteration (sink sum, contribution sum, delta max):
    # one compile each, every later iteration a cache hit.
    assert res.compiles == 3
    assert sess.stats.calls == 30
    assert sess.stats.cache_hits == 27
    ref = pagerank_reference(edges, 64, tol=0.0, max_iters=10)
    assert float(np.abs(res.scores - ref).max() / ref.max()) < 1e-4


def test_kmeans_10_iters_one_compile_per_config():
    pts, _ = cluster_points(2000, 3, 4, seed=0)
    init = pts[:4].copy()
    sess = BlazeSession()
    res = kmeans(pts, 4, init_centers=init, tol=0.0, max_iters=10, session=sess)
    assert res.iterations == 10
    # 2 configs: the assignment step (10×) and the final inertia pass (1×).
    assert res.compiles == 2
    assert sess.stats.calls == 11
    assert sess.stats.cache_hits == 9
    ref_centers, _ = kmeans_reference(pts, init, tol=0.0, max_iters=10)
    assert float(np.abs(res.centers - ref_centers).max()) < 1e-2


def test_gmm_one_compile_per_config():
    pts, _ = cluster_points(600, 2, 3, seed=1)
    sess = BlazeSession()
    res = gmm_em(pts, 3, init_mu=pts[:3].copy(), tol=0.0, max_iters=5,
                 session=sess)
    assert res.iterations == 5
    # 4 MapReduce configs: log-likelihood, N_k, Σwx, Σw(x−μ)(x−μ)ᵀ.
    assert res.compiles == 4
    assert sess.stats.calls == 20
    assert sess.stats.cache_hits == 16


# -- engine="auto" policy + pallas in the compile cache ------------------------


def _dyn_key_mapper(i, x, emit):
    # key comes from data → dynamic (no static-key fast path)
    emit(x[0].astype(jnp.int32), x[1])


def _pts_rows(n=64, kmod=8, seed=0):
    rng = np.random.RandomState(seed)
    rows = rng.randn(n, 2).astype(np.float32)
    rows[:, 0] = rng.randint(0, kmod, n)
    return rows


def test_auto_picks_pallas_for_small_dense_key_range():
    from repro.core.session import PALLAS_AUTO_MAX_KEYS

    sess = BlazeSession()
    pts = distribute(_pts_rows())
    _, st = sess.map_reduce(
        pts, _dyn_key_mapper, "sum", jnp.zeros((8,), jnp.float32),
        engine="auto", return_stats=True,
    )
    assert st.engine == "pallas"
    # beyond the VMEM-resident bound → eager
    _, st = sess.map_reduce(
        pts, _dyn_key_mapper, "sum",
        jnp.zeros((PALLAS_AUTO_MAX_KEYS + 1,), jnp.float32),
        engine="auto", return_stats=True,
    )
    assert st.engine == "eager"


def test_auto_picks_hash_kernel_and_falls_back_for_custom_reducers():
    from repro.core import custom_reducer, make_dist_hashmap
    from repro.core.session import resolve_engine
    from repro.core.reducers import get_reducer

    sess = BlazeSession()
    pts = distribute(_pts_rows())
    # auto on a VMEM-sized hash target → the hash-aggregation kernel
    hm = make_dist_hashmap(sess.mesh, 128, (), jnp.float32, "sum")
    _, st = sess.map_reduce(
        pts, _dyn_key_mapper, "sum", hm, engine="auto", return_stats=True
    )
    assert st.engine == "pallas"
    # explicit pallas on a hash target runs the kernel too (no fallback)
    hm2 = make_dist_hashmap(sess.mesh, 128, (), jnp.float32, "sum")
    _, st = sess.map_reduce(
        pts, _dyn_key_mapper, "sum", hm2, engine="pallas", return_stats=True
    )
    assert st.engine == "pallas"
    # ... but an over-VMEM-sized table resolves auto to eager
    big = make_dist_hashmap(sess.mesh, 8192, (), jnp.float32, "sum")
    assert resolve_engine("auto", big, get_reducer("sum")) == "eager"
    # custom reducer has no pallas_segment/pallas_hash impl → eager
    maxish = custom_reducer(
        "maxish", jnp.maximum, lambda dt: jnp.asarray(-jnp.inf, dt)
    )
    _, st = sess.map_reduce(
        pts, _dyn_key_mapper, maxish,
        jnp.full((8,), -jnp.inf, jnp.float32),
        engine="auto", return_stats=True,
    )
    assert st.engine == "eager"
    # ... and explicit pallas with a custom reducer also reports the eager
    # plan that actually runs (and reuses its executable, not a duplicate)
    _, st = sess.map_reduce(
        pts, _dyn_key_mapper, maxish,
        jnp.full((8,), -jnp.inf, jnp.float32),
        engine="pallas", return_stats=True,
    )
    assert st.engine == "eager"
    assert st.compiles == 0 and st.cache_hits == 1


def test_unknown_engine_rejected():
    import pytest

    sess = BlazeSession()
    with pytest.raises(ValueError, match="unknown engine"):
        sess.map_reduce(
            DistRange(0, 8, 1), _sq_mapper, "sum", jnp.zeros((4,), jnp.int32),
            engine="spark",
        )


def test_compile_cache_key_includes_engine_choice():
    sess = BlazeSession()
    pts = distribute(_pts_rows())
    t8 = jnp.zeros((8,), jnp.float32)
    sess.map_reduce(pts, _dyn_key_mapper, "sum", t8, engine="eager",
                    return_stats=True)  # compile 1
    sess.map_reduce(pts, _dyn_key_mapper, "sum", t8, engine="pallas",
                    return_stats=True)  # compile 2
    # auto resolves to pallas for K=8 → must HIT the pallas entry, not compile
    _, st = sess.map_reduce(
        pts, _dyn_key_mapper, "sum", t8, engine="auto", return_stats=True
    )
    assert st.engine == "pallas"
    assert st.compiles == 0 and st.cache_hits == 1
    assert sess.stats.compiles == 2
    assert sess.cache_info()["entries"] == 2


def test_pallas_compiles_stay_flat_across_10_iterations():
    sess = BlazeSession()
    pts = distribute(_pts_rows())
    t8 = jnp.zeros((8,), jnp.float32)
    for i in range(10):
        _, st = sess.map_reduce(
            pts, _dyn_key_mapper, "sum", t8, engine="pallas",
            return_stats=True,
        )
        assert st.compiles == (1 if i == 0 else 0)
        assert st.cache_hits == (0 if i == 0 else 1)
        stf = st.finalize()
        assert stf.kernel_block_n is not None
        assert 0.0 < stf.kernel_occupancy <= 1.0
    assert sess.stats.compiles == 1
    assert sess.stats.cache_hits == 9


def test_pagerank_pallas_10_iters_one_compile_per_config():
    """Mirror of the eager PageRank count: pallas keys the same cache."""
    sess = BlazeSession()
    edges = rmat_edges(6, 8, seed=3)  # 64 nodes
    res = pagerank(edges, 64, tol=0.0, max_iters=10, engine="pallas",
                   session=sess)
    assert res.iterations == 10
    assert res.compiles == 3
    assert sess.stats.calls == 30
    assert sess.stats.cache_hits == 27
    ref = pagerank_reference(edges, 64, tol=0.0, max_iters=10)
    assert float(np.abs(res.scores - ref).max() / ref.max()) < 1e-4


def test_kmeans_pallas_matches_eager_and_reference():
    pts, _ = cluster_points(2000, 3, 4, seed=0)
    init = pts[:4].copy()
    sess = BlazeSession()
    res = kmeans(pts, 4, init_centers=init, tol=0.0, max_iters=10,
                 engine="pallas", session=sess)
    assert res.iterations == 10
    assert res.compiles == 2
    ref_centers, _ = kmeans_reference(pts, init, tol=0.0, max_iters=10)
    assert float(np.abs(res.centers - ref_centers).max()) < 1e-2


# -- fused programs: N iterations = 1 program compile, ≤ ⌈N/unroll⌉ dispatches -

PROGRAM_ENGINES = ("eager", "pallas", "naive")


@pytest.mark.parametrize("engine", PROGRAM_ENGINES)
def test_pagerank_program_10_iters_one_compile_two_dispatches(engine):
    sess = BlazeSession()
    edges = rmat_edges(6, 8, seed=3)  # 64 nodes
    res = pagerank(edges, 64, tol=0.0, max_iters=10, engine=engine,
                   session=sess, mode="program", unroll=5)
    assert res.iterations == 10
    # The whole 3-op iteration is ONE executable: a single program compile,
    # and 10 iterations ship as ⌈10/5⌉ = 2 dispatches / 2 host syncs —
    # versus 30 dispatches + 10 syncs for the per-op loop.
    assert res.program_compiles == 1
    assert res.dispatches == 2
    assert res.host_syncs == 2
    assert res.compiles == 0  # no per-op executables were built
    assert sess.stats.calls == 0
    assert sess.stats.program_compiles == 1
    assert sess.stats.program_dispatches == 2
    ref = pagerank_reference(edges, 64, tol=0.0, max_iters=10)
    assert float(np.abs(res.scores - ref).max() / ref.max()) < 1e-4


@pytest.mark.parametrize("engine", PROGRAM_ENGINES)
def test_kmeans_program_10_iters_one_compile_two_dispatches(engine):
    pts, _ = cluster_points(2000, 3, 4, seed=0)
    init = pts[:4].copy()
    sess = BlazeSession()
    res = kmeans(pts, 4, init_centers=init, tol=0.0, max_iters=10,
                 engine=engine, session=sess, mode="program", unroll=5)
    assert res.iterations == 10
    assert res.program_compiles == 1
    # ⌈10/5⌉ = 2 fused-loop dispatches + the final inertia probe, which is
    # one more dispatch of the SAME fused executable (the assignment pass
    # carries the inertia since the plan refactor) — no per-op executable
    # is ever built, and the probe's host materialisation is counted.
    assert res.dispatches == 3
    assert sess.stats.program_dispatches == 3
    assert res.host_syncs == 3
    assert res.compiles == 0
    if engine != "naive":  # naive's wide shuffle is 3 gathers, not one psum
        assert res.collectives_per_iter == 1  # one [K, d+2] psum per iter
    ref_centers, _ = kmeans_reference(pts, init, tol=0.0, max_iters=10)
    assert float(np.abs(res.centers - ref_centers).max()) < 1e-2
    # the probe makes program-mode inertia exact w.r.t. the final centres
    per_op = kmeans(pts, 4, init_centers=init, tol=0.0, max_iters=10,
                    engine=engine, session=BlazeSession())
    assert abs(res.inertia - per_op.inertia) <= 1e-4 * abs(per_op.inertia)


@pytest.mark.parametrize("engine", PROGRAM_ENGINES)
def test_gmm_program_10_iters_one_compile_two_dispatches(engine):
    pts, _ = cluster_points(600, 2, 3, seed=1)
    init = pts[:3].copy()
    sess = BlazeSession()
    res = gmm_em(pts, 3, init_mu=init, tol=0.0, max_iters=10, engine=engine,
                 session=sess, mode="program", unroll=5)
    assert res.iterations == 10
    assert res.program_compiles == 1
    assert res.dispatches == 2
    assert res.host_syncs == 2
    assert res.compiles == 0
    ra, rm, rs, rll, _ = gmm_em_reference(pts, 3, init, tol=0.0, max_iters=10)
    assert float(np.abs(res.mu - rm).max()) < 1e-2
    assert float(np.abs(res.alpha - ra).max()) < 1e-3
    assert abs(res.log_likelihood - rll) / abs(rll) < 1e-3


def test_program_unroll_extremes_match_per_op_counts():
    """unroll=1 → one dispatch+sync per iteration (but still 1 compile);
    unroll=10 → one dispatch+sync total; per-op → 30 dispatches, 10 syncs."""
    edges = rmat_edges(6, 8, seed=3)
    ref = pagerank_reference(edges, 64, tol=0.0, max_iters=10)

    for unroll, want_disp in ((1, 10), (10, 1), (4, 3)):
        sess = BlazeSession()
        res = pagerank(edges, 64, tol=0.0, max_iters=10, session=sess,
                       mode="program", unroll=unroll)
        assert res.program_compiles == 1, unroll
        assert res.dispatches == want_disp, unroll
        assert res.host_syncs == want_disp, unroll
        assert float(np.abs(res.scores - ref).max() / ref.max()) < 1e-4

    sess = BlazeSession()
    res = pagerank(edges, 64, tol=0.0, max_iters=10, session=sess)
    assert res.dispatches == 30  # 3 ops × 10 iterations
    assert res.host_syncs == 10  # one float(delta) per iteration
    assert res.program_compiles == 0


def test_program_int8_wire_pagerank_matches_reference():
    """wire="int8" inside a fused program carries error-feedback residuals
    (quantize_with_feedback) across the device-resident iterations."""
    sess = BlazeSession()
    edges = rmat_edges(6, 8, seed=5)
    res = pagerank(edges, 64, tol=0.0, max_iters=10, wire="int8",
                   session=sess, mode="program", unroll=5)
    ref = pagerank_reference(edges, 64, tol=0.0, max_iters=10)
    assert res.program_compiles == 1 and res.dispatches == 2
    assert float(np.abs(res.scores - ref).max() / ref.max()) < 2e-2


def test_program_convergence_stops_early_on_block_boundary():
    sess = BlazeSession()
    edges = rmat_edges(6, 8, seed=3)
    res = pagerank(edges, 64, tol=1e-3, max_iters=100, session=sess,
                   mode="program", unroll=4)
    assert res.converged
    assert res.iterations % 4 == 0  # host test runs only every `unroll` steps
    assert res.dispatches == res.iterations // 4
    per_op = pagerank(edges, 64, tol=1e-3, max_iters=100)
    # fused loop may overshoot convergence by < one block, never undershoot
    assert per_op.iterations <= res.iterations < per_op.iterations + 4
