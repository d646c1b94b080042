"""The benchmark's one traffic generator: TPC-C's input law, from a seed.

A traffic mix is a data file beside this module (``<name>.json``) that sets
the law's parameters; this module reads it and draws chunks. Nothing here
imports the program: the harness wraps the arrays into the program's batch
types, and the reference reads the same arrays.

The law (TPC-C v5.11 §2.4.1, §2.5.1, §2.6.1, §2.8.1, with the departures a
configuration lists under ``assumed``):

* New-Order: home warehouse uniform over the chip's warehouses, district
  uniform, customer uniform (not NURand), 5..15 lines, quantity 1..10, item
  uniform over the catalog or Zipf(theta) with item id == popularity rank,
  and each line supplied by a uniformly drawn *other* warehouse with
  probability ``remote_frac``;
* Payment: warehouse, district and customer uniform, amount uniform in
  [1, 5000], always home (0% remote);
* Order-Status: warehouse, district and customer uniform;
* Stock-Level: warehouse and district uniform, threshold 10..20.

Chunk ``k`` is drawn from ``SeedSequence([seed, k])`` alone, so any chunk
can be made again in any order, and every seed draws the same sizes.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import NamedTuple

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent


@dataclasses.dataclass(frozen=True)
class Mix:
    """One traffic mix: the parameters of the law, per chip and step."""

    name: str
    chunk_steps: int            # executor steps per chunk (scan length)
    chunks_per_round: int       # chunks handed to one run call
    neworders_per_step: int     # per chip
    payments_per_step: int      # per chip
    read_frac: float            # Order-Status and Stock-Level, each, per NO
    remote_frac: float          # share of order lines from another warehouse
    item_dist: str              # "uniform" or "zipf"
    zipf_theta: float = 0.0

    @property
    def reads_per_step(self) -> int:
        return max(1, int(self.neworders_per_step * self.read_frac))


def load_mix(name: str, directory: Path = TRAFFIC_DIR) -> Mix:
    """The mix ``<name>.json`` beside this module."""
    with open(directory / f"{name}.json") as f:
        raw = json.load(f)
    fields = {f.name for f in dataclasses.fields(Mix)}
    unknown = set(raw) - fields
    if unknown:
        raise ValueError(f"traffic {name!r}: unknown keys {sorted(unknown)}")
    mix = Mix(name=name, **{k: v for k, v in raw.items() if k != "name"})
    if mix.item_dist not in ("uniform", "zipf"):
        raise ValueError(f"traffic {name!r}: item_dist {mix.item_dist!r}")
    return mix


class Shape(NamedTuple):
    """What the law needs of the deployment."""

    n_warehouses: int
    districts: int
    customers: int
    n_items: int
    max_lines: int
    n_shards: int


class Chunk(NamedTuple):
    """``chunk_steps`` steps of the mix; the batch axis is shard-major
    (shard ``r`` holds rows ``r*B .. (r+1)*B``), as the engine splits it."""

    no_w: np.ndarray        # [T, R*B] int32
    no_d: np.ndarray        # [T, R*B] int32
    no_c: np.ndarray        # [T, R*B] int32
    no_n_lines: np.ndarray  # [T, R*B] int32
    no_i_id: np.ndarray     # [T, R*B, L] int32
    no_supply_w: np.ndarray  # [T, R*B, L] int32
    no_qty: np.ndarray      # [T, R*B, L] int32
    no_ts: np.ndarray       # [T, R*B] int32
    pay_w: np.ndarray       # [T, R*P] int32
    pay_d: np.ndarray
    pay_c: np.ndarray
    pay_amount: np.ndarray  # [T, R*P] float32
    os_w: np.ndarray        # [T, R*Q] int32
    os_d: np.ndarray
    os_c: np.ndarray
    sl_w: np.ndarray        # [T, R*Q] int32
    sl_d: np.ndarray
    sl_threshold: np.ndarray


class Generator:
    """Draws chunk ``k`` of a run from ``(seed, k)``."""

    def __init__(self, mix: Mix, shape: Shape, seed: int):
        if shape.n_warehouses % shape.n_shards:
            raise ValueError("warehouses must divide evenly over the chips")
        self.mix, self.shape = mix, shape
        # SeedSequence takes non-negative integers of any size
        self.seed = int(seed) % (1 << 64)
        self._cdf = None
        if mix.item_dist == "zipf":
            p = 1.0 / np.power(np.arange(1, shape.n_items + 1,
                                         dtype=np.float64), mix.zipf_theta)
            self._cdf = np.cumsum(p / p.sum())

    def chunk(self, k: int) -> Chunk:
        mix, sh = self.mix, self.shape
        rng = np.random.default_rng(np.random.SeedSequence([self.seed, k]))
        T, R, L = mix.chunk_steps, sh.n_shards, sh.max_lines
        B, P, Q = mix.neworders_per_step, mix.payments_per_step, \
            mix.reads_per_step
        W, D, C, I = sh.n_warehouses, sh.districts, sh.customers, sh.n_items
        Wl = W // R
        i32 = np.int32

        def home(n):   # [T, R*n] warehouses homed on each shard
            lo = (np.arange(R) * Wl)[None, :, None]
            return (lo + rng.integers(0, Wl, (T, R, n))).reshape(T, R * n)

        no_w = home(B)
        n_lines = rng.integers(5, L + 1, (T, R * B))
        if self._cdf is None:
            i_id = rng.integers(0, I, (T, R * B, L))
        else:
            i_id = np.minimum(np.searchsorted(self._cdf,
                                              rng.random((T, R * B, L))),
                              I - 1)
        remote = rng.random((T, R * B, L)) < mix.remote_frac
        other = (no_w[..., None] + 1
                 + rng.integers(0, max(W - 1, 1), (T, R * B, L))) % W
        supply = np.where(remote & (W > 1), other, no_w[..., None])
        qty = rng.integers(1, 11, (T, R * B, L))
        # logical timestamps rise through the whole run: chunk, step, shard
        ts = (np.arange(T)[:, None] + k * T) * (R * B) + np.arange(R * B)
        pay_w = home(P)
        os_w = home(Q)
        sl_w = home(Q)
        return Chunk(
            no_w=no_w.astype(i32),
            no_d=rng.integers(0, D, (T, R * B)).astype(i32),
            no_c=rng.integers(0, C, (T, R * B)).astype(i32),
            no_n_lines=n_lines.astype(i32),
            no_i_id=i_id.astype(i32),
            no_supply_w=supply.astype(i32),
            no_qty=qty.astype(i32),
            no_ts=ts.astype(i32),
            pay_w=pay_w.astype(i32),
            pay_d=rng.integers(0, D, (T, R * P)).astype(i32),
            pay_c=rng.integers(0, C, (T, R * P)).astype(i32),
            pay_amount=rng.uniform(1.0, 5000.0, (T, R * P)).astype(
                np.float32),
            os_w=os_w.astype(i32),
            os_d=rng.integers(0, D, (T, R * Q)).astype(i32),
            os_c=rng.integers(0, C, (T, R * Q)).astype(i32),
            sl_w=sl_w.astype(i32),
            sl_d=rng.integers(0, D, (T, R * Q)).astype(i32),
            sl_threshold=rng.integers(10, 21, (T, R * Q)).astype(i32),
        )
