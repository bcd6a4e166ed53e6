"""The benchmark's device generators hold the semantics of
``repro.data.synthetic`` (the Zipf bounds, the blob spread, the R-MAT bit
rule), at tiny sizes on the CPU."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.jobs import kmeans, pagerank, wordcount
from repro.data import synthetic


def test_corpus_lines_hold_bounded_zipf_words_then_padding():
    vocab, lanes, min_words, n = 512, 128, 64, 256
    lines = np.asarray(wordcount.corpus(
        jax.random.key(7), n_lines=n, lanes=lanes, vocab=vocab,
        min_words=min_words, s=1.0,
    ))
    assert lines.shape == (n, lanes) and lines.dtype == np.int32
    live = lines >= 0
    lens = live.sum(axis=1)
    assert lens.min() >= min_words and lens.max() <= lanes
    # every line is its words then -1 padding, as zipf_corpus lays it out
    assert (live == (np.arange(lanes)[None, :] < lens[:, None])).all()
    assert (lines[~live] == wordcount.EMPTY).all()
    words = lines[live]
    assert words.min() >= 0 and words.max() < vocab
    # bounded Zipf, s = 1: P(rank r) = 1 / (r * H_vocab)
    h = np.sum(1.0 / np.arange(1, vocab + 1))
    freq = np.bincount(words, minlength=vocab) / words.size
    assert freq[0] == pytest.approx(1 / h, abs=0.01)
    assert freq[0] / freq[1] == pytest.approx(2.0, rel=0.1)


def test_zipf_cdf_ends_at_one():
    cdf = np.asarray(wordcount.zipf_cdf(32768, 1.0))
    assert cdf[-1] == 1.0 and (np.diff(cdf) >= 0).all()


def test_blobs_spread_around_their_centres():
    n, dim, k, spread, scale = 1 << 15, 4, 5, 0.35, 2.0
    key = jax.random.key(3)
    pts = np.asarray(kmeans.blobs(key, n, dim, k, spread, scale))
    centres = scale * np.asarray(
        jax.random.normal(jax.random.split(key, 3)[0], (k, dim))
    )
    d2 = ((pts[:, None, :] - centres[None]) ** 2).sum(-1)
    near = d2.argmin(1)
    resid = pts - centres[near]
    assert resid.std() == pytest.approx(spread, rel=0.03)
    assert np.bincount(near, minlength=k).min() > n / k * 0.9
    # the same law as cluster_points: centres of scale 2, spread 0.35
    ref, _ = synthetic.cluster_points(n, dim, k, spread=spread, seed=3)
    assert pts.std() == pytest.approx(ref.std(), rel=0.5)


def test_rmat_bits_follow_the_rule_of_rmat_edges():
    scale, n_edges, a, b, c = 6, 4096, 0.57, 0.19, 0.19
    key = jax.random.key(11)
    src, dst = (np.asarray(x) for x in pagerank.rmat_bits(key, scale, n_edges, a, b, c))
    for bit in range(scale):
        r = np.asarray(jax.random.uniform(jax.random.fold_in(key, bit), (n_edges,)))
        want_src = r >= a + b
        want_dst = ((r >= a) & (r < a + b)) | (r >= a + b + c)
        assert ((src >> bit) & 1 == want_src).all()
        assert ((dst >> bit) & 1 == want_dst).all()
    # and the quadrant shares match the numpy generator's
    ref = synthetic.rmat_edges(scale, n_edges >> scale, a=a, b=b, c=c, seed=1)
    for ours, theirs in ((src, ref[:, 0]), (dst, ref[:, 1])):
        assert ((ours & 1).mean()) == pytest.approx((theirs & 1).mean(), abs=0.03)


def test_graph_permutes_labels_and_counts_degrees():
    scale, ef = 8, 16
    key = jax.random.key(5)
    edges, deg = (np.asarray(x) for x in pagerank.graph(
        key, scale=scale, edge_factor=ef, a=0.57, b=0.19, c=0.19))
    n = 1 << scale
    assert edges.shape == (n * ef, 2)
    assert edges.min() >= 0 and edges.max() < n
    assert (deg == np.bincount(edges[:, 0], minlength=n)).all()
    # a permutation of the labels keeps the multiset of out-degrees
    src, _ = pagerank.rmat_bits(jax.random.split(key)[0], scale, n * ef,
                                0.57, 0.19, 0.19)
    plain = np.bincount(np.asarray(src), minlength=n)
    assert sorted(plain) == sorted(deg)
    assert not (plain == deg).all()


def test_generators_take_seeds_past_32_bits():
    big = 2**31 + 12345
    a = np.asarray(wordcount.corpus(jax.random.key(big), n_lines=8, lanes=128,
                                    vocab=64, min_words=64, s=1.0))
    b = np.asarray(wordcount.corpus(jax.random.key(big), n_lines=8, lanes=128,
                                    vocab=64, min_words=64, s=1.0))
    c = np.asarray(wordcount.corpus(jax.random.key(big + 1), n_lines=8, lanes=128,
                                    vocab=64, min_words=64, s=1.0))
    assert (a == b).all() and not (a == c).all()
    assert jnp.asarray(a).dtype == jnp.int32
