"""Multi-host scale-out: topology helpers, the ``hierarchical-collectives``
pass, and hierarchical-vs-flat equivalence on a simulated 2-D mesh.

In-process tests cover the pure pieces (simulate helpers, wire-byte
accounting, the plan pass, plan hash/render stability on 1-D meshes).
Subprocess tests spawn workers with ``launch.simulate.simulated_env(8)`` —
8 simulated CPU devices arranged as ``("node", "data")`` meshes — and hold
the hierarchical reduce to the same laws the fault suite uses: bit-equality
with the flat wire (integer-valued payloads), dict/NumPy-oracle exactness,
and intra/inter wire-byte accounting that matches the combine-edge model.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax.numpy as jnp

from repro.launch import simulate

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, n_devices: int = 8) -> dict:
    env = simulate.simulated_env(
        n_devices, pythonpath=os.path.join(ROOT, "src")
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, env=env,
        timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


# -- launch/simulate helpers --------------------------------------------------


def test_host_device_flags_fresh_and_replace():
    assert simulate.host_device_flags(8) == (
        "--xla_force_host_platform_device_count=8"
    )
    # an existing count is replaced, unrelated flags survive
    got = simulate.host_device_flags(
        4, "--xla_cpu_foo=1 --xla_force_host_platform_device_count=512"
    )
    assert got.split() == [
        "--xla_cpu_foo=1", "--xla_force_host_platform_device_count=4"
    ]
    with pytest.raises(ValueError):
        simulate.host_device_flags(0)


def test_forced_host_device_count_parses_env():
    env = {"XLA_FLAGS": "--xla_force_host_platform_device_count=16"}
    assert simulate.forced_host_device_count(env) == 16
    assert simulate.forced_host_device_count({"XLA_FLAGS": ""}) is None
    assert simulate.forced_host_device_count({}) is None


def test_simulated_env_is_the_worker_recipe():
    base = {"XLA_FLAGS": "--xla_cpu_foo=1", "PYTHONPATH": "/elsewhere"}
    env = simulate.simulated_env(8, base, pythonpath="/src")
    assert "--xla_force_host_platform_device_count=8" in env["XLA_FLAGS"]
    assert "--xla_cpu_foo=1" in env["XLA_FLAGS"]
    assert env["PYTHONPATH"].split(os.pathsep) == ["/src", "/elsewhere"]
    assert env["JAX_PLATFORMS"] == "cpu"  # never contends for a chip
    assert base == {"XLA_FLAGS": "--xla_cpu_foo=1", "PYTHONPATH": "/elsewhere"}


def test_force_host_device_count_after_backend_init_raises():
    import jax

    jax.devices()  # ensure the backend is up in this process
    with pytest.raises(RuntimeError, match="backend"):
        simulate.force_host_device_count(8)


# -- wire-byte accounting -----------------------------------------------------


def test_wire_bytes_derive_from_dtype():
    from repro.distributed.collectives import wire_bytes

    x32 = jnp.zeros((100,), jnp.float32)
    assert wire_bytes(x32, "none") == 400
    # "none" reads the element width off the dtype — no hardcoded 4
    assert wire_bytes(np.zeros((100,), np.float64), "none") == 800
    assert wire_bytes(np.zeros((100,), np.int16), "none") == 200
    assert wire_bytes(x32, "bf16") == 200


def test_wire_bytes_int8_frames_ship_their_scales():
    from repro.distributed.collectives import wire_bytes

    x = jnp.zeros((100,), jnp.float32)
    assert wire_bytes(x, "int8") == 100 + 4  # lattice + one shared f32 scale
    assert wire_bytes(x, "int8", n_scales=3) == 100 + 12  # per-block format
    with pytest.raises(ValueError):
        wire_bytes(x, "int8", n_scales=0)
    with pytest.raises(ValueError):
        wire_bytes(x, "fp4")


def test_reduce_edge_bytes_combine_edge_model():
    from repro.core.mapreduce import reduce_edge_bytes

    # 1-node mesh: every edge intra, inter is exactly 0
    assert reduce_edge_bytes(10, 4, 4, 8, 1, False) == (10 * 4 * 7, 0)
    assert reduce_edge_bytes(10, 4, 4, 8, 1, True) == (10 * 4 * 7, 0)
    # flat on 2 nodes: topology-oblivious, all 7 edges inter
    assert reduce_edge_bytes(10, 4, 4, 8, 2, False) == (0, 10 * 4 * 7)
    # hier on 2 nodes: 6 intra edges full width, 1 inter edge wire width
    assert reduce_edge_bytes(10, 4, 1, 8, 2, True) == (10 * 4 * 6, 10 * 1)
    # hier on 4 nodes: 4 intra, 3 inter
    assert reduce_edge_bytes(10, 4, 2, 8, 4, True) == (10 * 4 * 4, 10 * 2 * 3)


# -- the hierarchical-collectives pass (plan layer, no devices needed) --------


def _node(n_nodes, *, engine="eager", hierarchical=True, wire="none",
          red_name="sum"):
    from repro.core.plan import build_mapreduce_node
    from repro.core.reducers import get_reducer

    return build_mapreduce_node(
        idx=0, kind="range", src="range[0:64:1]", source_key=None,
        mapper=lambda v, emit: emit(0, v), red=get_reducer(red_name),
        target=jnp.zeros((4,), jnp.float32), engine=engine, wire=wire,
        key_range=None, env=None, n_nodes=n_nodes, hierarchical=hierarchical,
    )


def test_pass_rewrites_eligible_nodes_only():
    assert _node(1).hier is False  # 1-D mesh: strict no-op
    n = _node(2)
    assert n.hier is True
    assert n.collective == "psum[node×data, hier]"
    assert _node(2, engine="naive").hier is False  # no reduction tree
    assert _node(2, hierarchical=False).hier is False  # A/B baseline off
    n8 = _node(4, wire="int8")
    assert n8.collective == "psum[node×data, hier, wire=int8@inter]"
    # non-sum wired reduces never narrow — no @inter suffix
    assert _node(2, red_name="min").collective == "min-reduce[node×data, hier]"


def test_hier_node_is_a_distinct_plan_identity():
    """The hier rewrite lands BEFORE tune_key/stable_desc capture: a
    hierarchical node must not alias the flat node's tuning winners or plan
    hash (they compile different collectives)."""
    flat, hier = _node(1), _node(2)
    assert flat.stable_desc() != hier.stable_desc()
    assert flat.tune_key != hier.tune_key
    assert hier.stable_desc().endswith(" hier")


def test_plan_hash_and_render_multinode():
    from repro.core.plan import single_op_plan

    p1 = single_op_plan(_node(1), n_shards=8)
    p2 = single_op_plan(_node(2), n_shards=8, n_nodes=2)
    assert p1.hash != p2.hash
    r1, r2 = p1.render(), p2.render()
    # legacy 1-D rendering is untouched (explain goldens pin this)
    assert "node[" not in r1 and "hierarchical-collectives" not in r1
    assert "mesh: node[2]×data[4]" in r2
    assert "passes: resolve-engines, hierarchical-collectives" in r2
    assert "psum[node×data, hier]" in r2


# -- compat + mesh construction -----------------------------------------------


def test_distributed_initialize_single_process_noop():
    from repro import compat

    assert compat.distributed_initialize() is False
    assert compat.process_count() == 1
    assert compat.process_index() == 0


def test_make_node_data_mesh_shapes_8dev():
    res = _run(
        """
import json, jax
from repro.launch.mesh import make_node_data_mesh, init_distributed
import repro.core.containers as C
assert len(jax.devices()) == 8
out = {"shapes": {}, "err": None}
for n in (1, 2, 4, 8):
    m = make_node_data_mesh(n)
    out["shapes"][str(n)] = [dict(m.shape)["node"], dict(m.shape)["data"]]
    assert C.n_nodes(m) == n and C.shard_count(m) == 8
    assert C.data_axes(m) == ("node", "data")
try:
    make_node_data_mesh(3)
except ValueError as e:
    out["err"] = str(e)
out["initialized"] = init_distributed()  # single process: graceful no-op
print(json.dumps(out))
"""
    )
    assert res["shapes"] == {
        "1": [1, 8], "2": [2, 4], "4": [4, 2], "8": [8, 1]
    }
    assert "3 node" in res["err"]  # the error names the bad split
    assert res["initialized"] is False


# -- hierarchical vs flat on a simulated 2-D mesh -----------------------------


def test_hier_matches_flat_and_oracle_8dev():
    """Per-op dense reduces on (2,4) and (4,2) meshes: the hierarchical wire
    is bit-equal to the flat wire and to the NumPy oracle for sum (integer-
    valued floats — associativity-proof), min and max; stats report the
    intra/inter split of the combine-edge model; explain renders the
    topology."""
    res = _run(
        """
import json, numpy as np, jax, jax.numpy as jnp
from repro.core.session import BlazeSession
from repro.launch.mesh import make_node_data_mesh

vals = np.random.RandomState(0).randint(-50, 50, (64, 4)).astype(np.float32)

def m(i, row, emit):
    emit(0, row)

out = {}
for n_nodes in (2, 4):
    s = BlazeSession(mesh=make_node_data_mesh(n_nodes))
    v = s.distribute(vals)
    r = {}
    for red, oracle in (("sum", vals.sum(0)), ("min", vals.min(0)),
                        ("max", vals.max(0))):
        t = jnp.zeros((1, 4), jnp.float32) if red == "sum" else (
            jnp.full((1, 4), np.inf if red == "min" else -np.inf, jnp.float32))
        hier, st_h = s.map_reduce(v, m, red, t, return_stats=True)
        flat, st_f = s.map_reduce(v, m, red, t, return_stats=True,
                                  hierarchical=False)
        st_h, st_f = st_h.finalize(), st_f.finalize()
        r[red] = {
            "bit_equal": np.asarray(hier).tobytes() == np.asarray(flat).tobytes(),
            "oracle": bool(np.array_equal(np.asarray(hier)[0], oracle)),
            "intra": int(st_h.intra_bytes), "inter": int(st_h.inter_bytes),
            "flat_intra": int(st_f.intra_bytes),
            "flat_inter": int(st_f.inter_bytes),
            "coll": st_h.collective, "flat_coll": st_f.collective,
        }
    out[str(n_nodes)] = r
print(json.dumps(out))
"""
    )
    for n_nodes in (2, 4):
        r = res[str(n_nodes)]
        for red in ("sum", "min", "max"):
            assert r[red]["bit_equal"], (n_nodes, red, r[red])
            assert r[red]["oracle"], (n_nodes, red)
            # combine-edge model: 4 f32 elements, 8 shards
            assert r[red]["intra"] == 16 * (8 - n_nodes)
            assert r[red]["inter"] == 16 * (n_nodes - 1)
            assert r[red]["flat_intra"] == 0
            assert r[red]["flat_inter"] == 16 * 7
            assert "hier" in r[red]["coll"]
            assert "hier" not in r[red]["flat_coll"]
        # hier moves strictly fewer inter-node bytes than flat
        assert r["sum"]["inter"] < r["sum"]["flat_inter"]


def test_hier_int8_wire_narrows_inter_only_8dev():
    """A wired hierarchical sum quantises the inter-node hop only: fewer
    quantisation addends (one per node) than the flat compressed wire, so
    the error can only shrink — and inter bytes drop to the int8 frame."""
    res = _run(
        """
import json, numpy as np, jax, jax.numpy as jnp
from repro.core.session import BlazeSession
from repro.launch.mesh import make_node_data_mesh

vals = np.random.RandomState(1).randn(64, 8).astype(np.float32)
exact = vals.sum(0)

def m(i, row, emit):
    emit(0, row)

s = BlazeSession(mesh=make_node_data_mesh(2))
v = s.distribute(vals)
t = jnp.zeros((1, 8), jnp.float32)
hier, st_h = s.map_reduce(v, m, "sum", t, wire="int8", return_stats=True)
flat, st_f = s.map_reduce(v, m, "sum", t, wire="int8", return_stats=True,
                          hierarchical=False)
st_h, st_f = st_h.finalize(), st_f.finalize()
scale = float(np.abs(exact).max())
print(json.dumps({
    "hier_err": float(np.abs(np.asarray(hier)[0] - exact).max()) / scale,
    "flat_err": float(np.abs(np.asarray(flat)[0] - exact).max()) / scale,
    "intra": int(st_h.intra_bytes), "inter": int(st_h.inter_bytes),
    "flat_inter": int(st_f.inter_bytes),
    "coll": st_h.collective,
}))
"""
    )
    assert res["hier_err"] < 0.05 and res["flat_err"] < 0.05
    assert res["coll"] == "psum[node×data, hier, wire=int8@inter]"
    # intra edges at full f32 width, the single inter edge at int8 width
    assert res["intra"] == 8 * 4 * 6
    assert res["inter"] == 8 * 1 * 1
    assert res["inter"] < res["flat_inter"] == 8 * 1 * 7


def test_program_hier_vs_flat_bit_equal_8dev():
    """The fused-program path on a (2,4) mesh: hierarchical and flat builds
    of the same step converge bit-equal on integer-valued sums, and the
    plans differ exactly by the hierarchical-collectives pass."""
    res = _run(
        """
import json, numpy as np, jax, jax.numpy as jnp
from repro.core.session import BlazeSession
from repro.launch.mesh import make_node_data_mesh

vals = np.random.RandomState(2).randint(0, 100, (64, 4)).astype(np.float32)

def m(i, row, emit):
    emit(0, row)

s = BlazeSession(mesh=make_node_data_mesh(2))
v = s.distribute(vals)

def step(ctx, state):
    t = ctx.map_reduce(v, m, "sum", jnp.zeros((1, 4), jnp.float32))
    return {"acc": state["acc"] + t[0]}

state0 = {"acc": jnp.zeros((4,), jnp.float32)}
p_h = s.program(step)
p_f = s.program(step, hierarchical=False)
out_h = p_h(dict(state0), 3)
out_f = p_f(dict(state0), 3)
exp = 3 * vals.sum(0)
print(json.dumps({
    "bit_equal": np.asarray(out_h["acc"]).tobytes()
                 == np.asarray(out_f["acc"]).tobytes(),
    "oracle": bool(np.array_equal(np.asarray(out_h["acc"]), exp)),
    "hash_differs": p_h.plan.hash != p_f.plan.hash,
    "render_h": s.explain(p_h, dict(state0)),
    "render_f": s.explain(p_f, dict(state0)),
}))
"""
    )
    assert res["bit_equal"] and res["oracle"] and res["hash_differs"]
    assert "hierarchical-collectives" in res["render_h"]
    assert "psum[node×data, hier]" in res["render_h"]
    assert "hierarchical-collectives" not in res["render_f"]


def test_collective_inter_fault_retries_bit_equal_8dev():
    """``collective.inter`` (the slow cross-host hop) is a supervised fault
    point: an injected transient on the inter-node leg retries and the
    retried dispatch is bit-identical to the fault-free run."""
    res = _run(
        """
import json, numpy as np, jax, jax.numpy as jnp
from repro.core import faults
from repro.core.session import BlazeSession
from repro.launch.mesh import make_node_data_mesh

faults.reset(env=False)
FAST = faults.RetryPolicy(attempts=3, backoff_s=0.0, multiplier=1.0,
                          deadline_s=None)
vals = np.random.RandomState(3).randint(0, 100, (64, 4)).astype(np.float32)

def m(i, row, emit):
    emit(0, row)

mesh = make_node_data_mesh(2)
t = jnp.zeros((1, 4), jnp.float32)
ref_s = BlazeSession(mesh=mesh, retry=FAST)
ref = ref_s.map_reduce(ref_s.distribute(vals), m, "sum", t)
# The point fires while the hierarchical reduce traces, so arm it before
# the session's first compile of this op (a cache hit never re-traces).
s = BlazeSession(mesh=mesh, retry=FAST)
v = s.distribute(vals)
faults.configure("collective.inter", at=1)
got = s.map_reduce(v, m, "sum", t)
snap = faults.snapshot()
print(json.dumps({
    "bit_equal": np.asarray(got).tobytes() == np.asarray(ref).tobytes(),
    "retries": s.stats.retries,
    "balanced": snap["balanced"],
    "retried": snap["dispositions"]["retried"],
}))
"""
    )
    assert res["bit_equal"]
    assert res["retries"] == 1
    assert res["balanced"] and res["retried"] == 1


def test_compressed_psum_hierarchical_8dev():
    """``compressed_psum(..., intra_axis=)`` under shard_map on a (2,4)
    mesh: exact for wire="none" (bit-equal to the flat psum), close for
    int8, and ``psum_with_feedback``'s hierarchical residual is replicated
    within each node (every member computes the same node-level error)."""
    res = _run(
        """
import json, numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.compat import shard_map
from repro.distributed.collectives import compressed_psum, psum_with_feedback
from repro.launch.mesh import make_node_data_mesh

mesh = make_node_data_mesh(2)
x = jnp.asarray(np.random.RandomState(0).randn(8, 128).astype(np.float32))
exact = np.asarray(x).sum(0)
spec = P(("node", "data"))
out = {}
for wire in ("none", "int8"):
    def hier_fn(v):
        return compressed_psum(v[0], "node", wire=wire, intra_axis="data")[None]
    def flat_fn(v):
        return compressed_psum(v[0], ("node", "data"), wire=wire)[None]
    got_h = jax.jit(shard_map(hier_fn, mesh=mesh, in_specs=spec,
                              out_specs=spec, check_vma=False))(x)
    got_f = jax.jit(shard_map(flat_fn, mesh=mesh, in_specs=spec,
                              out_specs=spec, check_vma=False))(x)
    scale = float(np.abs(exact).max())
    out[wire] = {
        "hier_err": float(np.abs(np.asarray(got_h)[0] - exact).max()) / scale,
        "flat_err": float(np.abs(np.asarray(got_f)[0] - exact).max()) / scale,
    }

def fb(v, r):
    red, nr = psum_with_feedback(v[0], r[0], "node", wire="int8",
                                 intra_axis="data")
    return red[None], nr[None]
res_fb, resid = jax.jit(shard_map(fb, mesh=mesh, in_specs=(spec, spec),
                                  out_specs=(spec, spec),
                                  check_vma=False))(x, jnp.zeros_like(x))
resid = np.asarray(resid)
# residual replicated within a node: shards (0..3) and (4..7) agree
out["resid_replicated"] = bool(
    np.array_equal(resid[0], resid[1]) and np.array_equal(resid[4], resid[7])
    and np.array_equal(resid[1], resid[3])
)
print(json.dumps(out))
"""
    )
    # full-precision hier psum reassociates the same addends: ulp-level only
    assert res["none"]["hier_err"] < 1e-6
    assert res["int8"]["hier_err"] < 0.05
    assert res["int8"]["flat_err"] < 0.05
    assert res["resid_replicated"]
