"""Plain reference for the TPC-C configurations: the deployment's initial
data law, a replay of the benchmark's transaction stream in numpy, and the
comparison that decides ``correct``.

It imports nothing of the program. It follows the semantics the
configurations state, step by step as the executor orders them:

* the run starts from TPC-C's initial population (``make_data``): the
  stock, and in every district the initial orders (the oldest delivered,
  the rest waiting in NEW-ORDER) and one HISTORY payment per customer;
* New-Order, Payment, Order-Status, Stock-Level, then one Delivery, per
  step; a chunk of steps ends in the outbox drain (and, under escrow, the
  share refresh);
* merge regime (``stock_invariant: restock``): every New-Order commits, and
  stock follows TPC-C §2.4.2.2 (``q - x`` if that is >= 10, else
  ``q - x + 91``), whose result over any grouping is the one value in
  [10, 100] congruent to ``q0 - total`` mod 91;
* escrow regime (``strict``): a hard ``s_quantity >= 0`` floor. Each chip
  admits its batch first-come-first-served in batch order; a transaction
  commits iff, on every cell it touches, its own demand plus the demand of
  the earlier committed transactions of the step fits the cell's headroom.
  Headroom is the chip's escrow share of a hot cell (the ``hot_items`` most
  popular items of every warehouse) less what it spent since the last
  refresh, or the stock of a cold cell the chip owns; a cold line of
  another chip's warehouse is admitted and settled at its owner in the
  drain, where a cell's drained cold lines land all together iff their sum
  fits its stock, and are otherwise all refused. Hot lines drain
  unconditionally. The refresh then splits each hot cell's stock into
  ``q // R`` per chip, the remainder to the lowest chips.

Escrow verdicts are checked, not replayed one by one: the program's claimed
verdicts (the committed orders its ORDER table holds) give each step's
cumulative committed demand per cell in one pass, and each transaction's
verdict is recomputed from the demand of the transactions before it. A
verdict depends only on earlier ones, so all verdicts agree iff they equal
the sequential replay's (by induction over the batch order), and the
reference's own verdicts drive every table it builds.
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np

COUNTERS = ("neworders", "aborts", "payments", "order_statuses",
            "stock_levels", "deliveries", "reads_found",
            "fractures_observed", "lines_repaired", "cold_rejects")

# integer and bool tables compared exactly; float tables by relative gap
INT_TABLES = ("d_next_o_id", "o_valid", "o_c_id", "o_ol_cnt", "o_carrier",
              "o_entry_d", "no_valid", "o_ts", "c_payment_cnt",
              "c_delivery_cnt", "s_quantity", "s_order_cnt", "s_remote_cnt",
              "ol_valid", "ol_vis", "ol_delivered", "ol_qty", "ol_ts")
# compared on valid order lines only: the program writes the whole row
VALID_LINE_TABLES = ("ol_i_id", "ol_supply_w")
FLOAT_TABLES = ("w_ytd", "d_ytd", "h_amount_sum", "c_balance",
                "c_ytd_payment", "c_delivered_sum", "s_ytd", "ol_amount")
ESCROW_TABLES = ("shares", "spent")

RESTOCK = 91


class Data(NamedTuple):
    """The deployment's initial data (TPC-C §4.3.3.1): the seeded columns,
    and the initial ORDER, NEW-ORDER and ORDER-LINE rows of every district.
    Order ``o`` (0-based) sits in ring slot ``o``; the last ``new_orders``
    of each district are undelivered. Each customer starts with one
    payment of ``payment`` in HISTORY (C_YTD_PAYMENT, C_PAYMENT_CNT 1,
    C_BALANCE ``-payment``, D_YTD and W_YTD the sums)."""

    s_quantity: np.ndarray   # [W, I] int32
    i_price: np.ndarray      # [I] float32
    w_tax: np.ndarray        # [W] float32
    d_tax: np.ndarray        # [W, D] float32
    c_discount: np.ndarray   # [W, D, C] float32
    o_c_id: np.ndarray       # [W, D, N0] int32, distinct customers
    o_ol_cnt: np.ndarray     # [W, D, N0] int32, 5..L
    o_carrier: np.ndarray    # [W, D, N0] int32, 1..10 delivered, else -1
    ol_i_id: np.ndarray      # [W, D, N0, L] int32 (0 past the order's lines)
    ol_amount: np.ndarray    # [W, D, N0, L] float32, 0 unless undelivered
    new_orders: int          # undelivered initial orders per district
    payment: float           # each customer's initial HISTORY amount

    @property
    def n_initial(self) -> int:
        return self.o_c_id.shape[2]


INITIAL_QTY = 5              # OL_QUANTITY of every initial order line


def make_data(cfg: dict, seed: int) -> Data:
    """Initial data from ``seed``, on a stream apart from the traffic's."""
    rng = np.random.default_rng(
        np.random.SeedSequence([int(seed) % (1 << 64), 1 << 40]))
    W, D, C, I = (cfg["n_warehouses"], cfg["districts"], cfg["customers"],
                  cfg["n_items"])
    L = cfg["max_lines"]
    N0 = cfg["initial_orders_per_district"]
    NO = cfg["initial_new_orders_per_district"]
    if not (0 <= NO <= N0 <= min(C, cfg["order_capacity"])):
        raise ValueError("initial orders: need 0 <= new orders <= orders "
                         "<= customers, order capacity")
    f32 = np.float32
    s_quantity = rng.integers(cfg["stock_min"], cfg["stock_max"] + 1,
                              (W, I), dtype=np.int32)
    i_price = rng.uniform(cfg["price_min"], cfg["price_max"], I).astype(f32)
    w_tax = rng.uniform(0.0, cfg["tax_max"], W).astype(f32)
    d_tax = rng.uniform(0.0, cfg["tax_max"], (W, D)).astype(f32)
    c_discount = rng.uniform(0.0, cfg["discount_max"], (W, D, C)).astype(f32)
    # O_C_ID: a permutation of the district's customers
    o_c_id = rng.permuted(np.broadcast_to(np.arange(C, dtype=np.int32),
                                          (W, D, C)), axis=2)[..., :N0]
    o_ol_cnt = rng.integers(5, L + 1, (W, D, N0), dtype=np.int32)
    delivered = np.arange(N0) < N0 - NO
    o_carrier = np.where(delivered, rng.integers(
        1, 11, (W, D, N0), dtype=np.int32), -1).astype(np.int32)
    valid = np.arange(L) < o_ol_cnt[..., None]
    ol_i_id = np.where(valid, rng.integers(0, I, (W, D, N0, L),
                                           dtype=np.int32), 0)
    ol_amount = np.zeros((W, D, N0, L), f32)
    ol_amount[:, :, N0 - NO:] = np.where(
        valid[:, :, N0 - NO:],
        rng.uniform(0.01, 9999.99, (W, D, NO, L)).astype(f32), 0)
    return Data(s_quantity, i_price, w_tax, d_tax, c_discount,
                o_c_id.astype(np.int32), o_ol_cnt, o_carrier,
                ol_i_id.astype(np.int32), ol_amount, NO,
                float(cfg["initial_payment"]))


def claims_from_orders(o_valid: np.ndarray, o_entry_d: np.ndarray):
    """The New-Orders a program's ORDER table says it committed (entry
    timestamps are unique per transaction; the initial orders' are
    negative)."""
    return np.sort(o_entry_d[o_valid & (o_entry_d >= 0)].astype(np.int64))


class Expected(NamedTuple):
    counters: np.ndarray        # [n_chunks, len(COUNTERS)] int64
    tables: dict                # name -> np.ndarray
    max_orders: int             # orders in the fullest district
    verdict_mismatch: int       # escrow: claimed verdicts that disagree


class _Orders:
    """Committed New-Orders in commit order, as column lists."""

    def __init__(self):
        self.cols = {k: [] for k in ("w", "d", "o_id", "c", "n", "ts",
                                     "rts", "i_id", "supply", "qty",
                                     "amount", "step")}

    def add(self, **cols):
        for k, v in cols.items():
            self.cols[k].append(v)

    def arrays(self, L):
        out = {}
        for k, v in self.cols.items():
            if v:
                out[k] = np.concatenate(v)
            else:
                out[k] = np.zeros((0, L) if k in ("i_id", "supply", "qty",
                                                  "amount") else (0,),
                                  np.float32 if k == "amount" else np.int64)
        return out


class _Sums:
    """Per-index sums of one or more value columns, gathered in parts."""

    def __init__(self):
        self.idx, self.vals = [], []

    def add(self, idx, *vals):
        idx = np.asarray(idx, np.int64)
        self.idx.append(idx)
        self.vals.append([np.broadcast_to(np.asarray(v), idx.shape)
                          for v in vals])

    def totals(self, n):
        if not self.idx:
            return [np.zeros(n)]
        idx = np.concatenate(self.idx)
        cols = zip(*self.vals)
        return [np.bincount(idx, np.concatenate(c).astype(np.float64), n)
                .astype(np.int64 if np.concatenate(c).dtype.kind in "biu"
                        else np.float64)
                for c in cols]

    def counts(self, n):
        idx = np.concatenate(self.idx) if self.idx else np.zeros(0, np.int64)
        return np.bincount(idx, minlength=n)


def _group_rank(keys: np.ndarray) -> np.ndarray:
    """Rank of each element among the earlier elements with its key."""
    order = np.argsort(keys, kind="stable")
    ks = keys[order]
    start = np.r_[True, ks[1:] != ks[:-1]]
    first = np.maximum.accumulate(np.where(start, np.arange(ks.size), 0))
    rank = np.empty_like(order)
    rank[order] = np.arange(ks.size) - first
    return rank


def replay(cfg: dict, data: Data, chunks, n_shards: int,
           claimed_ts: np.ndarray | None = None) -> Expected:
    """Replay ``chunks`` (the generator's, in run order) from ``data``."""
    W, D, C, I = (cfg["n_warehouses"], cfg["districts"], cfg["customers"],
                  cfg["n_items"])
    L, OC = cfg["max_lines"], cfg["order_capacity"]
    strict = cfg["engine"]["stock_invariant"] == "strict"
    H = min(cfg["engine"]["hot_items"], I) if strict else 0
    R = n_shards
    Wl = W // R

    q0 = data.s_quantity.astype(np.int64)
    q = q0.copy()                        # escrow: the live stock
    price = data.i_price.astype(np.float32)
    # sums whose order cannot matter are gathered as (index, value) parts
    # and added up once at the end
    stock = _Sums()                      # cell -> qty, lines, remote lines
    pay = _Sums()                        # customer -> amount
    dlv = _Sums()                        # customer -> delivered amount

    # the initial orders: slots 0..N0-1, the last ``new_orders`` undelivered
    N0 = data.n_initial
    d_next = np.full((W, D), N0, np.int64)
    n_deliv = np.full((W, D), N0 - data.new_orders, np.int64)
    ord_c = np.zeros((W, D, OC), np.int64)
    ord_amt = np.zeros((W, D, OC))
    delivered = np.zeros((W, D, OC), bool)
    ord_c[:, :, :N0] = data.o_c_id
    ord_amt[:, :, :N0] = data.ol_amount.astype(np.float64).sum(-1)
    delivered[:, :, :N0 - data.new_orders] = True
    orders = _Orders()
    os_reqs = []                         # (chunk, step index, w, d, c)

    if strict:
        qh = q[:, :H].reshape(-1)
        shares = np.stack([qh // R + (r < qh % R) for r in range(R)])
        spent = np.zeros_like(shares)
    counters = np.zeros((len(chunks), len(COUNTERS)), np.int64)
    col = {k: i for i, k in enumerate(COUNTERS)}
    verdict_mismatch = 0
    lane = np.arange(L)
    step_index = 0

    for k, ch in enumerate(chunks):
        T, RB = ch.no_w.shape
        B = RB // R
        r_t = np.arange(RB) // B
        w_lo = r_t * Wl
        out_sup, out_i, out_q = [], [], []
        for s in range(T):
            w, d, c = (ch.no_w[s].astype(np.int64), ch.no_d[s],
                       ch.no_c[s])
            n = ch.no_n_lines[s]
            i_id = ch.no_i_id[s].astype(np.int64)
            sup = ch.no_supply_w[s].astype(np.int64)
            qty = ch.no_qty[s].astype(np.int64)
            ts = ch.no_ts[s].astype(np.int64)
            valid = lane[None, :] < n[:, None]
            local = (sup >= w_lo[:, None]) & (sup < (w_lo + Wl)[:, None])

            if strict:
                committed, bad = _escrow_admit(
                    valid, local, sup, i_id, qty, ts, r_t, q, shares, spent,
                    W, I, H, claimed_ts)
                verdict_mismatch += bad
            else:
                committed = np.ones(RB, bool)
            ok = valid & committed[:, None]

            # o_ids: the district's counter plus the committed rank
            ci = np.flatnonzero(committed)
            wd = w[ci] * D + d[ci]
            o_id = d_next[w[ci], d[ci]] + _group_rank(wd)
            d_next += np.bincount(wd, minlength=W * D).reshape(W, D)
            if o_id.size and o_id.max() >= OC:
                raise ValueError("the run outgrew the order ring: compare "
                                 "needs every committed order in place")
            amount = np.where(valid, price[i_id] * qty.astype(np.float32),
                              np.float32(0))
            ord_c[w[ci], d[ci], o_id] = c[ci]
            ord_amt[w[ci], d[ci], o_id] = amount[ci].astype(
                np.float64).sum(1)
            orders.add(w=w[ci], d=d[ci].astype(np.int64), o_id=o_id,
                       c=c[ci].astype(np.int64), n=n[ci].astype(np.int64),
                       ts=ts[ci], rts=ts[ci] * R + r_t[ci],
                       i_id=i_id[ci], supply=sup[ci], qty=qty[ci],
                       amount=amount[ci],
                       step=np.full(ci.size, step_index, np.int64))

            # stock: committed local lines now, the rest through the outbox
            m = ok & local
            cell = (sup * I + i_id)[m]
            if strict:
                np.subtract.at(q.reshape(-1), cell, qty[m])
            stock.add(cell, qty[m], 1, (sup != w[:, None])[m])
            m = ok & ~local
            out_sup.append(sup[m])
            out_i.append(i_id[m])
            out_q.append(qty[m])

            # Payment
            pw, pd, pc = ch.pay_w[s], ch.pay_d[s], ch.pay_c[s]
            amt = ch.pay_amount[s].astype(np.float64)
            pay.add((pw.astype(np.int64) * D + pd) * C + pc, amt)

            # Order-Status answers are settled once every order is known
            os_reqs.append((k, step_index, ch.os_w[s], ch.os_d[s],
                            ch.os_c[s]))

            # Delivery: the oldest undelivered order of every district
            has = d_next > n_deliv
            hw, hd = np.nonzero(has)
            ho = n_deliv[hw, hd]
            cust = ord_c[hw, hd, ho]
            dlv.add((hw * D + hd) * C + cust, ord_amt[hw, hd, ho])
            delivered[hw, hd, ho] = True
            n_deliv += has

            row = counters[k]
            row[col["neworders"]] += committed.sum()
            row[col["aborts"]] += RB - committed.sum()
            row[col["payments"]] += pw.size
            row[col["order_statuses"]] += ch.os_w[s].size
            row[col["stock_levels"]] += ch.sl_w[s].size
            row[col["deliveries"]] += has.sum()
            step_index += 1

        # the drain: every outbox line lands at its owner
        sup = np.concatenate(out_sup)
        ii = np.concatenate(out_i)
        qq = np.concatenate(out_q)
        cell = sup * I + ii
        if strict:
            hot = ii < H
            # per cell, the drained cold lines land together iff they fit
            cold_cells, inv = np.unique(cell[~hot], return_inverse=True)
            demand = np.bincount(inv, qq[~hot], cold_cells.size)
            fits = np.ones(cell.size, bool)
            fits[~hot] = (demand <= q.reshape(-1)[cold_cells])[inv]
            counters[k, col["cold_rejects"]] = (~fits).sum()
            cell, qq = cell[fits], qq[fits]
            np.subtract.at(q.reshape(-1), cell, qq)
            stock.add(cell, qq, 1, 1)
            if (q.reshape(-1)[cell] < 0).any():
                raise AssertionError("reference broke the stock floor")
            # the refresh: each hot cell's stock split over the chips
            qh = q[:, :H].reshape(-1)
            shares = np.stack([qh // R + (r < qh % R) for r in range(R)])
            spent = np.zeros_like(shares)
        else:
            stock.add(cell, qq, 1, 1)

    o = orders.arrays(L)
    # Order-Status finds the customer's latest order iff one was placed
    first_order_step = np.full(W * D * C, np.iinfo(np.int64).max)
    cust = (o["w"] * D + o["d"]) * C + o["c"]
    np.minimum.at(first_order_step, cust, o["step"])
    first_order_step.reshape(W, D, C)[
        np.arange(W)[:, None, None], np.arange(D)[None, :, None],
        data.o_c_id] = -1
    for k, g, ow, od, oc in os_reqs:
        key = (ow.astype(np.int64) * D + od) * C + oc
        counters[k, col["reads_found"]] += (first_order_step[key] <= g).sum()

    dec, lines, remote = stock.totals(W * I)
    if strict:
        q_final = q
    else:
        lo = cfg["stock_min"]
        q_final = lo + (q0 - dec.reshape(W, I) - lo) % RESTOCK
    # with each customer's initial HISTORY row
    paid = pay.totals(W * D * C)[0].reshape(W, D, C) + data.payment
    n_paid = pay.counts(W * D * C).reshape(W, D, C) + (data.payment > 0)
    got_dlv = dlv.totals(W * D * C)[0].reshape(W, D, C)
    n_dlv = dlv.counts(W * D * C).reshape(W, D, C)
    w_ytd = paid.sum((1, 2))
    d_ytd = paid.sum(2)

    M = int(d_next.max()) if d_next.size else 0
    tables = _order_tables(o, data, delivered, W, D, OC, L, M)
    tables.update(
        d_next_o_id=d_next, w_ytd=w_ytd, d_ytd=d_ytd, h_amount_sum=d_ytd,
        c_balance=got_dlv - paid, c_ytd_payment=paid, c_payment_cnt=n_paid,
        c_delivered_sum=got_dlv, c_delivery_cnt=n_dlv,
        s_quantity=q_final, s_ytd=dec.astype(np.float64).reshape(W, I),
        s_order_cnt=lines.reshape(W, I), s_remote_cnt=remote.reshape(W, I))
    if strict:
        tables.update(shares=shares, spent=spent)
    return Expected(counters, tables, M, verdict_mismatch)


def _escrow_admit(valid, local, sup, i_id, qty, ts, r_t, q, shares, spent,
                  W, I, H, claimed_ts):
    """One step's admission on every chip at once. Returns (verdicts,
    count of claimed verdicts that disagree). Updates ``spent`` and
    leaves ``q`` to the caller."""
    RB = valid.shape[0]
    hot = i_id < H
    cons = valid & (hot | local)            # remote cold lines always fit
    t = np.broadcast_to(np.arange(RB)[:, None], valid.shape)[cons]
    hk = (sup * H + i_id)[cons]             # hot cell index
    is_hot = hot[cons]
    r = np.broadcast_to(r_t[:, None], valid.shape)[cons]
    # one headroom domain: each chip's hot shares, then the cold stock
    key = np.where(is_hot, r * (W * H) + hk,
                   len(shares) * W * H + (sup * I + i_id)[cons])
    head = np.where(is_hot, shares[r, np.where(is_hot, hk, 0)]
                    - spent[r, np.where(is_hot, hk, 0)],
                    q.reshape(-1)[np.where(is_hot, 0, (sup * I + i_id)[cons])])
    qq = qty[cons]
    if claimed_ts is None:
        claimed = np.ones(RB, bool)
    elif claimed_ts.size == 0:
        claimed = np.zeros(RB, bool)
    else:
        pos = np.searchsorted(claimed_ts, ts)
        claimed = (pos < claimed_ts.size) & (
            claimed_ts[np.minimum(pos, claimed_ts.size - 1)] == ts)

    if not qq.size:
        return np.ones(RB, bool), int((~claimed).sum())
    order = np.lexsort((t, key))
    ks, ts_, qs, hs = key[order], t[order], qq[order], head[order]
    start = np.r_[True, (ks[1:] != ks[:-1]) | (ts_[1:] != ts_[:-1])]
    gi = np.flatnonzero(start)
    g_key, g_t, g_head = ks[gi], ts_[gi], hs[gi]
    g_dem = np.add.reduceat(qs, gi)
    g_claim = claimed[g_t] * g_dem
    excl = np.cumsum(g_claim) - g_claim
    kstart = np.r_[True, g_key[1:] != g_key[:-1]]
    first = np.maximum.accumulate(np.where(kstart, np.arange(gi.size), 0))
    fits = excl - excl[first] + g_dem <= g_head
    verdict = np.ones(RB, bool)
    verdict[g_t[~fits]] = False
    bad = int((verdict != claimed).sum())
    if bad:
        # a claim is wrong: the verdicts after it rest on wrong demand, so
        # replay the step one transaction at a time instead
        verdict = _fcfs(RB, ks, ts_, qs, hs)
        bad = int((verdict != claimed).sum())

    # spend the committed hot demand from each chip's shares
    m = verdict[t] & is_hot
    np.add.at(spent, (r[m], hk[m]), qq[m])
    return verdict, bad


def _fcfs(RB, keys, txn, qty, head):
    """Sequential first-come-first-served admission over the constrained
    lines of one step (sorted by key, then transaction)."""
    lines: dict[int, list] = {}
    for k, t, q, h in zip(keys.tolist(), txn.tolist(), qty.tolist(),
                          head.tolist()):
        lines.setdefault(t, []).append((k, q, h))
    used: dict[int, int] = {}
    verdict = np.ones(RB, bool)
    for t in sorted(lines):
        want: dict[int, int] = {}
        for k, q, h in lines[t]:
            want[k] = want.get(k, 0) + q
        heads = {k: h for k, _, h in lines[t]}
        if all(used.get(k, 0) + q <= heads[k] for k, q in want.items()):
            for k, q in want.items():
                used[k] = used.get(k, 0) + q
        else:
            verdict[t] = False
    return verdict


def _order_tables(o, data, delivered, W, D, OC, L, M):
    """ORDER, NEW-ORDER and ORDER-LINE as the program lays them out; the
    line tables cover the first ``M`` slots of every district. The initial
    orders fill slots ``0..N0-1`` with entry dates ``o - N0`` (older than
    every run's) and commit stamp 0."""
    w, d, s = o["w"], o["d"], o["o_id"]
    dl = delivered[w, d, s]
    t = dict(
        o_valid=np.zeros((W, D, OC), bool),
        o_c_id=np.zeros((W, D, OC), np.int32),
        o_ol_cnt=np.zeros((W, D, OC), np.int32),
        o_carrier=np.full((W, D, OC), -1, np.int32),
        o_entry_d=np.zeros((W, D, OC), np.int32),
        no_valid=np.zeros((W, D, OC), bool),
        o_ts=np.full((W, D, OC), -1, np.int32))
    N0 = data.n_initial
    dl0 = delivered[:, :, :N0]
    t["o_valid"][:, :, :N0] = True
    t["o_c_id"][:, :, :N0] = data.o_c_id
    t["o_ol_cnt"][:, :, :N0] = data.o_ol_cnt
    t["o_carrier"][:, :, :N0] = np.where(data.o_carrier >= 0, data.o_carrier,
                                         np.where(dl0, 1, -1))
    t["o_entry_d"][:, :, :N0] = np.arange(N0) - N0
    t["no_valid"][:, :, :N0] = ~dl0
    t["o_ts"][:, :, :N0] = 0
    t["o_valid"][w, d, s] = True
    t["o_c_id"][w, d, s] = o["c"]
    t["o_ol_cnt"][w, d, s] = o["n"]
    t["o_carrier"][w, d, s] = np.where(dl, 1, -1)
    t["o_entry_d"][w, d, s] = o["ts"]
    t["no_valid"][w, d, s] = ~dl
    t["o_ts"][w, d, s] = o["rts"]

    valid = np.arange(L)[None, :] < o["n"][:, None]
    shape = (W, D, M, L)
    ol = dict(
        ol_valid=np.zeros(shape, bool), ol_vis=np.zeros(shape, bool),
        ol_delivered=np.zeros(shape, bool),
        ol_qty=np.zeros(shape, np.int32),
        ol_ts=np.full(shape, -1, np.int32),
        ol_i_id=np.zeros(shape, np.int32),
        ol_supply_w=np.zeros(shape, np.int32),
        ol_amount=np.zeros(shape, np.float64))
    v0 = np.arange(L) < data.o_ol_cnt[..., None]
    ol["ol_valid"][:, :, :N0] = v0
    ol["ol_vis"][:, :, :N0] = v0
    ol["ol_delivered"][:, :, :N0] = v0 & dl0[..., None]
    ol["ol_qty"][:, :, :N0] = np.where(v0, INITIAL_QTY, 0)
    ol["ol_ts"][:, :, :N0] = np.where(v0, 0, -1)
    ol["ol_i_id"][:, :, :N0] = data.ol_i_id
    ol["ol_supply_w"][:, :, :N0] = np.arange(W)[:, None, None, None]
    ol["ol_amount"][:, :, :N0] = data.ol_amount
    ol["ol_valid"][w, d, s] = valid
    ol["ol_vis"][w, d, s] = valid
    ol["ol_delivered"][w, d, s] = valid & dl[:, None]
    ol["ol_qty"][w, d, s] = np.where(valid, o["qty"], 0)
    ol["ol_ts"][w, d, s] = np.where(valid, o["rts"][:, None], -1)
    ol["ol_i_id"][w, d, s] = o["i_id"]
    ol["ol_supply_w"][w, d, s] = o["supply"]
    ol["ol_amount"][w, d, s] = o["amount"]
    t.update(ol)
    return t


# ---------------------------------------------------------------------------
# The comparison
# ---------------------------------------------------------------------------


def compare(cfg: dict, data: Data, exp: Expected, got: dict,
            got_counters: np.ndarray, round_chunks: list[int]) -> dict:
    """The numbers that decide ``correct``, each a plain count or gap, and
    per table what was found (mismatch counts, float gaps).

    ``got`` holds the program's final tables (line tables over the first
    ``exp.max_orders`` slots, or more) and, under escrow, ``shares`` and
    ``spent``; ``got_counters`` is ``[n_rounds, len(COUNTERS)]``, round
    ``j`` having run ``round_chunks[j]`` chunks of the stream in order."""
    M = exp.max_orders
    detail = {}
    bounds = np.cumsum([0] + list(round_chunks))
    want = np.stack([exp.counters[a:b].sum(0)
                     for a, b in zip(bounds[:-1], bounds[1:])]) \
        if round_chunks else exp.counters[:0]
    table_mismatch = 0
    names = INT_TABLES + (ESCROW_TABLES if "shares" in exp.tables else ())
    for name in names:
        g = np.asarray(got[name])
        e = exp.tables[name]
        if name.startswith("ol_"):
            g = g[:, :, :M]
            # a line written past the reference's last order is a mismatch
            table_mismatch += _past_m(got[name], M, name)
        bad = int((g != e).sum()) if g.shape == e.shape else int(e.size)
        table_mismatch += bad
        if bad:
            detail[name] = bad
    valid = exp.tables["ol_valid"]
    for name in VALID_LINE_TABLES:
        g = np.asarray(got[name])[:, :, :M]
        bad = int(((g != exp.tables[name]) & valid).sum()) \
            if g.shape == valid.shape else int(valid.size)
        table_mismatch += bad
        if bad:
            detail[name] = bad

    float_gap = 0.0
    for name in FLOAT_TABLES:
        g = np.asarray(got[name]).astype(np.float64)
        e = exp.tables[name]
        if name == "ol_amount":
            g = g[:, :, :M]
        if g.shape != e.shape:
            float_gap = float("inf")
            continue
        scale = max(float(np.abs(e).max(initial=0.0)), 1.0)
        gap = float(np.abs(g - e).max(initial=0.0)) / scale
        detail[name] = gap
        float_gap = max(float_gap, gap)

    counter_mismatch = int((got_counters != want).sum()) \
        if got_counters.shape == want.shape else int(want.size)
    if counter_mismatch and got_counters.shape == want.shape:
        for i, k in enumerate(COUNTERS):
            bad = int((got_counters[:, i] != want[:, i]).sum())
            if bad:
                detail[f"counter {k}"] = bad

    # the guarantees, read straight off the program's final state
    q = np.asarray(got["s_quantity"]).astype(np.int64)
    col = {k: i for i, k in enumerate(COUNTERS)}
    laws = int(got_counters[:, col["fractures_observed"]].sum())
    if cfg["engine"]["stock_invariant"] == "strict":
        sold = np.rint(np.asarray(got["s_ytd"], np.float64)).astype(np.int64)
        laws += int((q < 0).sum())
        laws += int((q + sold != data.s_quantity).sum())
        H = min(cfg["engine"]["hot_items"], cfg["n_items"])
        remaining = (np.asarray(got["shares"], np.int64).sum(0)
                     - np.asarray(got["spent"], np.int64).sum(0))
        laws += int((remaining != q[:, :H].reshape(-1)).sum())
    else:
        laws += int(((q < cfg["stock_min"]) | (q > cfg["stock_max"])).sum())

    checks = {"verdict_mismatch": exp.verdict_mismatch,
              "table_mismatch": table_mismatch,
              "counter_mismatch": counter_mismatch,
              "law_violations": laws,
              "float_gap": float_gap}
    return checks, detail


def _past_m(arr, M, name) -> int:
    """Lines set beyond slot ``M`` of any district (fetched tables may be
    wider than the reference's)."""
    a = np.asarray(arr)
    if a.shape[2] <= M:
        return 0
    tail = a[:, :, M:]
    default = -1 if name == "ol_ts" else 0
    return int((tail != default).sum()) if name in (
        "ol_valid", "ol_vis", "ol_delivered", "ol_qty", "ol_ts") else 0
