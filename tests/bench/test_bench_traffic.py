"""The traffic generator's law: deterministic from the seed, and its
marginals are TPC-C's."""

import numpy as np
import pytest

from bench.traffic.generator import Generator, Shape, load_mix

SHAPE = Shape(n_warehouses=8, districts=10, customers=3000, n_items=1000,
              max_lines=15, n_shards=2)


def draws(mix_name, seed, n_chunks=6):
    gen = Generator(load_mix(mix_name), SHAPE, seed)
    return [gen.chunk(k) for k in range(n_chunks)]


@pytest.mark.parametrize("mix", ["uniform", "zipf1"])
def test_same_seed_same_traffic_any_order(mix):
    a = draws(mix, 2**31 + 5)
    gen = Generator(load_mix(mix), SHAPE, 2**31 + 5)
    b = [gen.chunk(k) for k in reversed(range(6))][::-1]
    for x, y in zip(a, b):
        for f in x._fields:
            np.testing.assert_array_equal(getattr(x, f), getattr(y, f))
    c = draws(mix, 2**31 + 6)
    assert not np.array_equal(a[0].no_i_id, c[0].no_i_id)


def test_sizes_do_not_depend_on_seed():
    for seed in (0, 1, 2**40 + 3):
        ch = draws("uniform", seed, 1)[0]
        assert ch.no_w.shape == (8, 2 * 256)
        assert ch.no_i_id.shape == (8, 2 * 256, 15)
        assert ch.pay_w.shape == (8, 2 * 256)
        assert ch.os_w.shape == ch.sl_w.shape == (8, 2 * 23)


def test_marginals_uniform():
    chs = draws("uniform", 11, 20)
    lines = np.concatenate([c.no_n_lines.ravel() for c in chs])
    assert lines.min() == 5 and lines.max() == 15
    assert abs(lines.mean() - 10.0) < 0.1
    qty = np.concatenate([c.no_qty.ravel() for c in chs])
    assert qty.min() == 1 and qty.max() == 10
    home = np.concatenate([np.broadcast_to(c.no_w[..., None],
                                           c.no_supply_w.shape).ravel()
                           for c in chs])
    supply = np.concatenate([c.no_supply_w.ravel() for c in chs])
    remote = (supply != home).mean()
    assert 0.008 < remote < 0.012                     # 1% of lines
    items = np.concatenate([c.no_i_id.ravel() for c in chs])
    counts = np.bincount(items, minlength=1000)
    assert counts.max() < 2.0 * counts.mean()        # no hot items
    amount = np.concatenate([c.pay_amount.ravel() for c in chs])
    assert amount.min() >= 1.0 and amount.max() <= 5000.0
    thr = np.concatenate([c.sl_threshold.ravel() for c in chs])
    assert thr.min() == 10 and thr.max() == 20


def test_marginals_zipf():
    chs = draws("zipf1", 12, 20)
    items = np.concatenate([c.no_i_id.ravel() for c in chs])
    p = 1.0 / np.arange(1, 1001)
    p /= p.sum()
    freq = np.bincount(items, minlength=1000) / items.size
    # id == popularity rank: the first items take Zipf(1)'s shares
    np.testing.assert_allclose(freq[:4], p[:4], rtol=0.05)


def test_home_warehouses_and_timestamps():
    ch = draws("uniform", 13, 1)[0]
    B = 256
    assert (ch.no_w[:, :B] < 4).all() and (ch.no_w[:, B:] >= 4).all()
    assert (ch.pay_w[:, :B] < 4).all() and (ch.pay_w[:, B:] >= 4).all()
    ts = np.concatenate([c.no_ts.ravel() for c in draws("uniform", 13, 3)])
    assert (np.diff(ts) == 1).all() and ts[0] == 0
    # a remote line's warehouse is another warehouse
    rem = ch.no_supply_w != ch.no_w[..., None]
    assert rem.any()
