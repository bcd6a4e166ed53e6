"""The engine-path Pallas kernels compile for a v5e chip (no chip needed).

The TPU compiler is installed with JAX: it compiles for a described
``v5e:2x2`` topology whose devices are not attached.  Each test lowers one
kernel with ``interpret=False`` at the sizes the smoke run uses and checks
that Mosaic accepted it: the executable holds a ``tpu_custom_call``.
Interpret-mode tests cannot see what these catch (unsupported shape casts,
unaligned blocks, VMEM overflow).

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import hash_combine as HK
from repro.kernels import segment_reduce as SK


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler to describe it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _compiled_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize(
    "n,k,v,reducer,dtype",
    [
        (1 << 20, 4096, 1, "sum", jnp.float32),  # MXU one-hot path, K=4096
        (1 << 20, 256, 1, "sum", jnp.int32),  # exact select-scatter sum
        (100_000_000, 5, 4, "min", jnp.float32),  # k-means-sized stream
    ],
)
def test_segment_reduce_compiles_for_v5e(one_chip, n, k, v, reducer, dtype):
    text = _compiled_text(
        lambda ids, vals: SK.segment_reduce(
            ids, vals, k, reducer=reducer, interpret=False
        ),
        one_chip, ((n,), jnp.int32), ((n, v), dtype),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize(
    "n,v,reducer,dtype,hint",
    [
        (69_120_000, 1, "sum", jnp.int32, None),  # word count, open vocab
        (1 << 20, 1, "sum", jnp.float32, 500),  # distinct_hint sizes the table
        (1 << 16, 2, "max", jnp.float32, None),  # select-scatter, V=2
    ],
)
def test_hash_aggregate_compiles_for_v5e(one_chip, n, v, reducer, dtype, hint):
    cap, bn, probes = HK.choose_table_cap(
        n, v, reducer, dtype, distinct_hint=hint
    )
    text = _compiled_text(
        lambda keys, vals: HK.hash_aggregate(
            keys, vals, cap, reducer=reducer, max_probes=probes, block_n=bn,
            interpret=False,
        ),
        one_chip, ((n,), jnp.int32), ((n, v), dtype),
    )
    assert "tpu_custom_call" in text


def test_hash_merge_into_target_table_compiles_for_v5e(one_chip):
    """The post-shuffle merge: received pairs into a word-count target table
    of a capacity that is not the tuner's own (2048 slots, vocab 512)."""
    cap = 2048
    text = _compiled_text(
        lambda keys, vals, tk, tv, ovf: HK.hash_aggregate(
            keys, vals, cap, reducer="sum", init=(tk, tv, ovf),
            max_probes=16, interpret=False,
        ),
        one_chip,
        ((1024,), jnp.int32), ((1024, 1), jnp.int32),
        ((cap,), jnp.int32), ((cap, 1), jnp.int32), ((), jnp.int32),
    )
    assert "tpu_custom_call" in text
