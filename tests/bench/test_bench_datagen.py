"""The device generator and the plain reference (``bench/datagen.py``,
``bench/reference.py``)."""
import jax
import numpy as np

import benchkit  # noqa: F401
from bench import datagen, reference
from repro.data import vectors

BIG_SEED = 2 ** 33 + 12345          # wider than 32 bits


def test_reference_top10_equals_numpy_exact_topk():
    coll = datagen.make(3, BIG_SEED, n=20_000, dim=64, n_learn=8,
                        n_pool=96, chunk=5_000)
    x = np.asarray(coll.x)
    want = vectors.exact_topk(coll.pool, x, 10)
    # 20,000 rows in blocks of 4,096: the scan and the tail block both run
    vals, got = reference.exact_topk(coll.pool, coll.x, 10, query_block=64,
                                     row_block=4096)
    assert got.shape == (96, 10)
    assert [set(r) for r in got] == [set(r) for r in want]
    assert np.all(np.diff(vals, axis=1) <= 0)          # best first
    np.testing.assert_allclose(
        vals, np.take_along_axis(coll.pool @ x.T, got, axis=1), rtol=1e-5)


def test_generator_is_deterministic_in_the_seed_and_never_repeats():
    def make(collection_seed, seed):
        return datagen.make(collection_seed, seed, n=2_000, dim=16,
                            n_learn=32, n_pool=4096, chunk=500)
    a, b, c, d = make(3, BIG_SEED), make(3, BIG_SEED), make(3, BIG_SEED + 1), \
        make(4, BIG_SEED)
    np.testing.assert_array_equal(np.asarray(a.x), np.asarray(b.x))
    np.testing.assert_array_equal(a.pool, b.pool)
    # a run's seed draws other queries over the same collection
    np.testing.assert_array_equal(np.asarray(a.x), np.asarray(c.x))
    np.testing.assert_array_equal(np.asarray(a.learn), np.asarray(c.learn))
    assert not np.array_equal(a.pool, c.pool)
    assert not np.array_equal(np.asarray(a.x), np.asarray(d.x))
    both = np.concatenate([a.pool, np.asarray(a.learn)])
    assert len(np.unique(both, axis=0)) == len(both)
    assert np.isfinite(both).all() and np.isfinite(np.asarray(a.x)).all()


def test_seed_key_separates_the_high_bits():
    k0 = datagen.seed_key(5)
    k1 = datagen.seed_key(5 + 2 ** 32)
    assert not np.array_equal(jax.random.key_data(k0),
                              jax.random.key_data(k1))
