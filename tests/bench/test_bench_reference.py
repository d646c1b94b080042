"""The plain reference on its own: the restock rule's closed form and the
escrow verdict check."""

import numpy as np
import pytest

from bench.reference import tpcc as ref


def test_restock_closed_form_is_the_spec_rule():
    rng = np.random.default_rng(0)
    q0 = rng.integers(10, 101, 500)
    orders = rng.integers(1, 11, (500, 40))
    q = q0.copy()
    for col in orders.T:           # TPC-C 2.4.2.2, one line at a time
        q = np.where(q - col >= 10, q - col, q - col + 91)
    closed = 10 + (q0 - orders.sum(1) - 10) % ref.RESTOCK
    np.testing.assert_array_equal(q, closed)


def test_initial_population_follows_the_spec():
    """TPC-C 4.3.3.1: every customer places one of the district's initial
    orders, 5..15 lines each; the last ``new_orders`` are undelivered with
    a null carrier and priced lines, the rest delivered at amount 0."""
    cfg = dict(n_warehouses=3, districts=4, customers=50, n_items=200,
               max_lines=15, order_capacity=64, stock_min=10, stock_max=100,
               price_min=1.0, price_max=100.0, tax_max=0.2,
               discount_max=0.5, initial_orders_per_district=50,
               initial_new_orders_per_district=15, initial_payment=10.0)
    d = ref.make_data(cfg, 2**33 + 5)
    assert d.n_initial == 50 and d.new_orders == 15
    assert (np.sort(d.o_c_id, -1) == np.arange(50)).all()
    assert d.o_ol_cnt.min() >= 5 and d.o_ol_cnt.max() <= 15
    lines = np.arange(15) < d.o_ol_cnt[..., None]
    assert (d.ol_i_id[~lines] == 0).all() and d.ol_i_id.max() < 200
    assert (d.o_carrier[:, :, 35:] == -1).all()
    assert ((d.o_carrier[:, :, :35] >= 1) & (d.o_carrier[:, :, :35] <= 10)).all()
    assert (d.ol_amount[:, :, :35] == 0).all()
    assert (d.ol_amount[:, :, 35:][lines[:, :, 35:]] > 0).all()
    assert (d.ol_amount[~lines] == 0).all()
    again = ref.make_data(cfg, 2**33 + 5)
    assert all(np.array_equal(a, b) for a, b in zip(d, again))
    with pytest.raises(ValueError):
        ref.make_data(dict(cfg, initial_orders_per_district=51), 1)


def step(seed, W=2, I=50, H=5, R=1, B=32, L=15):
    rng = np.random.default_rng(seed)
    n = rng.integers(5, L + 1, R * B)
    valid = np.arange(L)[None, :] < n[:, None]
    i_id = np.minimum((rng.pareto(1.0, (R * B, L)) * 3).astype(int), I - 1)
    r_t = np.arange(R * B) // B
    sup = np.repeat(rng.integers(0, W, R * B)[:, None], L, 1)
    qty = rng.integers(1, 11, (R * B, L))
    q = rng.integers(20, 200, (W, I))
    shares = np.stack([rng.integers(20, 150, W * H) for _ in range(R)])
    return dict(valid=valid, local=np.ones_like(valid), sup=sup, i_id=i_id,
                qty=qty, ts=np.arange(R * B), r_t=r_t, q=q, shares=shares,
                spent=np.zeros_like(shares), W=W, I=I, H=H)


def sequential(a):
    """One transaction at a time, straight from the rule."""
    avail_hot = (a["shares"] - a["spent"])[0].copy()
    q = a["q"].copy()
    out = []
    for t in range(len(a["ts"])):
        need = {}
        for j in np.flatnonzero(a["valid"][t]):
            w, i, x = a["sup"][t, j], a["i_id"][t, j], a["qty"][t, j]
            key = ("h", w * a["H"] + i) if i < a["H"] else ("c", w, i)
            need[key] = need.get(key, 0) + x
        have = lambda k: avail_hot[k[1]] if k[0] == "h" else q[k[1], k[2]]
        ok = all(have(k) >= x for k, x in need.items())
        if ok:
            for k, x in need.items():
                if k[0] == "h":
                    avail_hot[k[1]] -= x
                else:
                    q[k[1], k[2]] -= x
        out.append(ok)
    return np.array(out)


def test_escrow_check_equals_sequential_admission():
    for seed in range(5):
        a = step(seed)
        want = sequential(a)
        assert 0 < want.sum() < want.size           # contention happens
        claims = np.sort(a["ts"][want])
        got, bad = ref._escrow_admit(claimed_ts=claims, **a)
        np.testing.assert_array_equal(got, want)
        assert bad == 0


def test_a_wrong_claim_is_counted_and_replayed():
    a = step(7)
    want = sequential(a)
    flipped = want.copy()
    flipped[np.flatnonzero(want)[0]] = False
    got, bad = ref._escrow_admit(claimed_ts=np.sort(a["ts"][flipped]),
                                 **step(7))
    np.testing.assert_array_equal(got, want)
    assert bad == 1
