"""Each plain reference of the benchmark agrees with the program's driver
(and its NumPy oracle) at a tiny size on the CPU, and its comparison reads
0 on the driver's answer."""
from __future__ import annotations

import jax
import numpy as np
import pytest

from bench.jobs import kmeans, pagerank, wordcount
from repro.core import BlazeSession, data_mesh

WC = {"corpus_lines": 512, "lanes": 128, "min_words": 64, "block_lines": 64,
      "vocab": 512, "zipf_s": 1.0}
KM = {"n_points": 1 << 14, "k": 5, "dim": 4, "spread": 0.35,
      "centre_scale": 2.0, "init_pool": 256, "steps_per_job": 4, "tol": 0.0}
PR = {"scale": 8, "edge_factor": 16, "a": 0.57, "b": 0.19, "c": 0.19,
      "damping": 0.85, "steps_per_job": 6, "tol": 0.0}


def test_wordcount_reference_matches_the_driver():
    from repro.core.algorithms import counts_dict
    from repro.core.algorithms import wordcount as driver

    traffic = {"blocks_per_job": 4, "engine": "eager"}
    data = wordcount.generate(WC, traffic, 21, data_mesh(1))
    j = 1  # blocks 4..7
    want = wordcount.reference(data, WC, traffic, j)
    lines = np.concatenate(data.blocks[4:8])
    assert want.sum() == (lines >= 0).sum() == data.words[4:8].sum()
    sess = BlazeSession(data_mesh(1))
    res = driver(sess.chunked(lines, WC["block_lines"]), vocab_size=WC["vocab"],
                 mode="program", engine="eager", session=sess)
    got = counts_dict(res.counts)
    assert got == {w: int(c) for w, c in enumerate(want) if c}
    t = res.counts.table
    ans = wordcount.Answer(np.asarray(t.keys), np.asarray(t.vals),
                           np.asarray(t.overflow))
    assert wordcount.compare(ans, want) == {"count_mismatch": 0, "overflow": 0}


def test_wordcount_comparison_counts_every_fault():
    want = np.array([3, 0, 2, 1])
    good = wordcount.as_answer(want)
    assert wordcount.compare(good, want) == {"count_mismatch": 0, "overflow": 0}
    free = wordcount.FREE_SLOT
    dup = wordcount.Answer(np.array([[0, 2, 3, 0]]), np.array([[1, 2, 1, 2]]),
                           np.array([0]))
    assert wordcount.compare(dup, want)["count_mismatch"] == 1  # key 0 twice
    off = wordcount.Answer(np.array([[0, 2, 3, free]]), np.array([[3, 2, 2, 0]]),
                           np.array([1]))
    assert wordcount.compare(off, want) == {"count_mismatch": 1, "overflow": 1}


def test_kmeans_reference_matches_the_numpy_oracle_and_the_driver():
    from repro.core.algorithms import kmeans as driver
    from repro.core.algorithms import kmeans_reference

    traffic = {"unroll": 2, "engine": "eager"}
    data = kmeans.generate(KM, traffic, 4, data_mesh(1))
    want = kmeans.reference(data, KM, traffic, 0)
    pts, init = np.asarray(data.points), np.asarray(data.init)
    oracle, _ = kmeans_reference(pts, init, tol=0.0,
                                 max_iters=KM["steps_per_job"])
    np.testing.assert_allclose(want[0], oracle, rtol=1e-5, atol=1e-5)
    res = driver(pts, KM["k"], init_centers=init, tol=0.0,
                 max_iters=KM["steps_per_job"], mode="program", engine="eager",
                 session=BlazeSession(data_mesh(1)))
    got = kmeans.compare((res.centers, res.inertia), want)
    assert got["centre_gap"] < 1e-5 and got["inertia_gap"] < 1e-5


def test_pagerank_reference_matches_the_numpy_oracle_and_the_driver():
    from repro.core.algorithms import pagerank as driver
    from repro.core.algorithms import pagerank_reference

    traffic = {"unroll": 3, "engine": "eager"}
    data = pagerank.generate(PR, traffic, 9, data_mesh(1))
    want = pagerank.reference(data, PR, traffic, 0)
    edges = np.asarray(data.edges)
    n = 1 << PR["scale"]
    oracle = pagerank_reference(edges, n, tol=0.0,
                                max_iters=PR["steps_per_job"])
    np.testing.assert_allclose(want, oracle, rtol=1e-5)
    res = driver(edges, n, tol=0.0, max_iters=PR["steps_per_job"],
                 mode="program", unroll=3, engine="eager",
                 session=BlazeSession(data_mesh(1)))
    assert pagerank.compare(res.scores, want)["score_gap"] < 1e-5


@pytest.mark.parametrize("kind", ["kmeans", "pagerank"])
def test_a_number_that_is_not_finite_reads_infinite(kind):
    if kind == "kmeans":
        want = (np.ones((2, 2)), 1.0)
        got = kmeans.compare((np.full((2, 2), np.nan), 1.0), want)
        assert got["centre_gap"] == float("inf")
    else:
        got = pagerank.compare(np.array([np.nan, 1.0]), np.array([1.0, 1.0]))
        assert got["score_gap"] == float("inf")


def test_references_run_in_the_lower_precision_asked_for():
    traffic = {"unroll": 3, "engine": "eager"}
    data = pagerank.generate(PR, traffic, 9, data_mesh(1))
    low = pagerank.reference(data, PR, traffic, 0, dtype=jax.numpy.bfloat16)
    want = pagerank.reference(data, PR, traffic, 0)
    assert pagerank.compare(low, want)["score_gap"] > 1e-3
