"""The harness's host-store path (``bench/datagen.py`` ``make_host``,
``bench/reference.py`` ``exact_topk_host``): the same collection and the
same reference as the device path, and never more than one chunk or block
of rows on the device."""
import numpy as np
import pytest

import benchkit  # noqa: F401  (puts the checkout on sys.path)
from bench import datagen, reference

SEED = 2 ** 33 + 4242
# the streamed reference scores each block against every query at once,
# the device one 1,024 queries at a time: the same float32 products, summed
# in another order, so a score may move by a few units in the last place
SCORE_RTOL = 1e-6


@pytest.mark.parametrize("chunk", [1024, 8192])
def test_host_collection_equals_the_device_collection(chunk):
    dev = datagen.make(7, SEED, n=8192, dim=32, n_learn=512, n_pool=20_000,
                       chunk=chunk)
    host = datagen.make_host(7, SEED, n=8192, dim=32, n_learn=512,
                             n_pool=20_000, chunk=chunk)
    assert isinstance(host.x, np.ndarray) and not host.x.flags.writeable
    pairs = [(host.x, np.asarray(dev.x)),
             (np.asarray(host.learn), np.asarray(dev.learn)),
             (host.pool, dev.pool),
             (datagen.with_host_pool(host, SEED + 1, 100).pool,
              datagen.with_pool(dev, SEED + 1, 100).pool)]
    if chunk < 8192:
        # the device loop runs the same chunk function: bit for bit
        for a, b in pairs:
            np.testing.assert_array_equal(a, b)
        return
    # one chunk is the whole collection: XLA folds the one-trip loop and
    # fuses the chunk's sums otherwise, so a value may move by one ulp of
    # the largest value, and a query by its anchor's share (0.4) of that
    ulp = float(np.spacing(np.abs(pairs[0][1]).max()))
    for a, b in pairs:
        np.testing.assert_allclose(a, b, rtol=0, atol=ulp)


def test_streamed_reference_equals_the_device_reference():
    coll = datagen.make(3, SEED, n=20_000, dim=64, n_learn=8, n_pool=3000,
                        chunk=5000)
    x = np.asarray(coll.x)
    # 20,000 rows in blocks of 4,096: the last block is padded
    v_dev, i_dev = reference.exact_topk(coll.pool, coll.x, 10,
                                        row_block=4096)
    v_host, i_host = reference.exact_topk_host(coll.pool, x, 10,
                                               row_block=4096)
    assert i_host.shape == (3000, 10)
    assert [set(r) for r in i_host] == [set(r) for r in i_dev]
    assert np.all(np.diff(v_host, axis=1) <= 0)          # best first
    scale = np.abs(v_dev[:, :1])
    assert np.all(np.abs(v_host - v_dev) <= SCORE_RTOL * scale)
    ids = i_dev.copy()
    ids[:, -1] = -1                                       # clipped
    ids[0, 0] = 10 ** 9
    np.testing.assert_allclose(
        reference.scores_of_host(coll.pool, x, ids),
        reference.scores_of(coll.pool, coll.x, ids),
        rtol=0, atol=SCORE_RTOL * float(scale.max()))


class _Rows:
    """Wraps the host path's jitted helpers and records the shape of the
    store's rows each one receives or makes (``(name, shape)``)."""

    # where each helper's rows are: an argument's index, or "out"
    ROWS = {"chunk_rows": "out", "anchor_ids": None, "ood_mix": 2,
            "fold_rows": 2, "scores_of_rows": 1}

    def __init__(self, monkeypatch):
        self.seen = []
        for name, at in self.ROWS.items():
            mod = datagen if hasattr(datagen, name) else reference
            monkeypatch.setattr(mod, name,
                                self._wrap(name, getattr(mod, name), at))

    def _wrap(self, name, fn, at):
        def call(*args, **kwargs):
            out = fn(*args, **kwargs)
            if at is not None:
                self.seen.append((name, (out if at == "out"
                                         else args[at]).shape))
            return out
        return call


def test_host_path_hands_the_device_one_chunk_or_block_at_a_time(
        monkeypatch):
    n, dim, chunk, block = 8192, 32, 1024, 2048
    rows = _Rows(monkeypatch)
    coll = datagen.make_host(7, SEED, n=n, dim=dim, n_learn=512, n_pool=0,
                             chunk=chunk)
    coll = datagen.with_host_pool(coll, SEED, 3000, pool_chunk=1024)
    reference.exact_topk_host(coll.pool, coll.x, 10, row_block=block)
    reference.scores_of_host(coll.pool, coll.x,
                             np.zeros((3000, 10), np.int32), block=128)
    assert {name for name, _ in rows.seen} == {
        "chunk_rows", "ood_mix", "fold_rows", "scores_of_rows"}
    assert rows.seen.count(("chunk_rows", (chunk, dim))) == n // chunk
    assert rows.seen.count(("fold_rows", (block, dim))) == n // block
    # no call saw more than one block of the store's rows (the answers'
    # rows, 128 queries x 10 ids, are under one block too)
    assert all(s[-1] == dim and int(np.prod(s[:-1])) <= block
               for _, s in rows.seen)


@pytest.mark.parametrize("store", ["store", "typo"])
def test_unknown_store_is_refused(store):
    from bench import run
    cfg = dict(benchkit.TINY_IVF, store=store)
    with pytest.raises(ValueError, match="unknown store"):
        run.placement(cfg)
