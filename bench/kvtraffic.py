"""Seeded YCSB traffic for the table benchmark.

One general generator reads a traffic mix (``bench/traffic/<name>.json``)
and a configuration (``bench/configs/<name>.json``) and makes, from
``--seed`` alone:

* the loaded records: distinct 64-bit keys (two uint32 words), each with
  room in its bucket, and their 8-byte values, in load order;
* the H3 matrix of the table's hash (the table's "weights");
* a pool of ``pool_ops`` operations, each a read or an update of a loaded
  key drawn zipfian over the loaded keys (YCSB's scrambled zipfian, theta
  0.99), and for an open loop the arrival gap before each.

A request is one operation, as a YCSB client thread sends it.  Request
``i`` is pool operation ``i % pool_ops``, so the window draws nothing: the
pool is made in set-up and the run cycles through it.  Every seed gets the
same number of updates in the pool and the same set of arrival gaps, in
another order, so the work in a window does not move with the seed.

Each request's value words carry ``(request index, random)``.  For an
update that is the new value, unique per request; for a read the table
ignores them, and the correctness check reads the request index back from
the dispatched slab to learn which step served the operation.
"""
from __future__ import annotations

import dataclasses

import numpy as np

import kvref

OP_SEARCH, OP_UPDATE = 1, 2         # the table's op codes (update == insert
                                    # of a present key)

_TRAFFIC_KEYS = {"source", "why", "loop", "clients", "ops_per_s",
                 "pool_ops", "read", "update", "distribution", "zipf_theta"}


@dataclasses.dataclass(frozen=True)
class Traffic:
    loop: str                        # "closed" or "open"
    read: float
    update: float
    zipf_theta: float
    pool_ops: int                    # operations made in set-up, cycled
    clients: int = 0                 # closed loop: one request outstanding each
    ops_per_s: float = 0.0           # open loop: offered operations per second

    @classmethod
    def from_json(cls, d: dict) -> "Traffic":
        extra = set(d) - _TRAFFIC_KEYS
        if extra:
            raise ValueError(f"unknown traffic keys {sorted(extra)}")
        if d.get("distribution") != "zipfian":
            raise ValueError("only the zipfian request distribution is made")
        if abs(d["read"] + d["update"] - 1.0) > 1e-9:
            raise ValueError("read + update must be 1")
        t = cls(loop=d["loop"], read=float(d["read"]),
                update=float(d["update"]), zipf_theta=float(d["zipf_theta"]),
                pool_ops=int(d["pool_ops"]),
                clients=int(d.get("clients", 0)),
                ops_per_s=float(d.get("ops_per_s", 0.0)))
        if t.loop not in ("closed", "open"):
            raise ValueError(f"loop must be closed or open, not {t.loop!r}")
        if t.loop == "closed" and t.clients < 1:
            raise ValueError("a closed loop needs clients")
        if t.loop == "open" and t.ops_per_s <= 0:
            raise ValueError("an open loop needs ops_per_s")
        if t.pool_ops < 1:
            raise ValueError("pool_ops must be positive")
        return t


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent numpy streams from one seed (any integer)."""
    return np.random.default_rng([int(seed) % (1 << 64), stream])


def distinct_keys(idx: np.ndarray, rng) -> np.ndarray:
    """``[n, 2]`` uint32 keys, distinct for distinct ``idx < 2**32``: word 0
    is an odd-multiplier bijection of the index, word 1 random."""
    w0 = (idx.astype(np.uint64) * np.uint64(0x9E3779B1)
          + np.uint64(0x7F4A7C15)) & np.uint64(0xFFFFFFFF)
    w1 = rng.integers(0, 2 ** 32, size=idx.shape, dtype=np.uint32)
    return np.stack([w0.astype(np.uint32), w1], axis=-1)


class Zipfian:
    """YCSB zipfian item draws over ``n`` items (rank 0 hottest), scrambled
    by a seeded permutation so the hot items are spread over the keys."""

    def __init__(self, n: int, theta: float, rng):
        self.cdf = np.cumsum(1.0 / np.arange(1, n + 1, dtype=np.float64)
                             ** theta)
        self.perm = rng.permutation(n)

    def draw(self, size: int, rng) -> np.ndarray:
        n = len(self.cdf)
        ranks = np.searchsorted(self.cdf, rng.random(size) * self.cdf[-1])
        return self.perm[np.minimum(ranks, n - 1)]


@dataclasses.dataclass
class Loaded:
    keys: np.ndarray                 # [n, 2] uint32, load order
    vals: np.ndarray                 # [n, 2] uint32
    q_masks: np.ndarray              # [index_bits, 2] uint32


def make_loaded(n: int, index_bits: int, key_words: int, val_words: int,
                slots: int, seed: int) -> Loaded:
    """``n`` records the table holds, in load order.  Keys are drawn in
    order, and one whose bucket already holds ``slots`` earlier keys is
    passed over, as a load that is refused there would leave it out: every
    record the run reads or updates is stored, and no operation fails."""
    if key_words != 2:
        raise ValueError("the YCSB keys are two uint32 words")
    rng = rng_for(seed, 1)
    q = rng_for(seed, 2).integers(0, 2 ** 32, size=(index_bits, key_words),
                                  dtype=np.uint32)
    drawn = distinct_keys(np.arange(n + n // 4 + 64), rng)
    fits = np.flatnonzero(kvref.bulk_load(drawn, q, slots))
    if len(fits) < n:
        raise ValueError(f"{len(fits)} of {len(drawn)} drawn keys have room "
                         f"in their bucket; {n} are to be loaded")
    keys = drawn[fits[:n]]
    vals = rng.integers(1, 2 ** 32, size=(n, val_words), dtype=np.uint32)
    return Loaded(keys, vals, q)


class RequestStream:
    """The run's one-operation requests, made in set-up as a pool that the
    window cycles through."""

    def __init__(self, traffic: Traffic, loaded: Loaded, seed: int):
        self.t = traffic
        P = traffic.pool_ops
        rng = rng_for(seed, 4)
        zipf = Zipfian(len(loaded.keys), traffic.zipf_theta, rng_for(seed, 6))
        kind = np.full(P, OP_SEARCH, np.int32)
        kind[:int(round(P * traffic.update))] = OP_UPDATE
        self.kind = rng.permutation(kind)
        self.kid = zipf.draw(P, rng)
        self.keys = loaded.keys[self.kid]
        self.words = rng.integers(1, 2 ** 32,
                                  size=(P, loaded.vals.shape[1] - 1),
                                  dtype=np.uint32)
        self.arrival = None              # open loop: [P] offsets in the cycle
        if traffic.loop == "open":
            q = (np.arange(P) + 0.5) / P
            gaps = -np.log1p(-q) / traffic.ops_per_s
            self.arrival = np.cumsum(rng_for(seed, 5).permutation(gaps))

    def request(self, i: int):
        """``(ops [1], keys [1, 2], vals [1, Wv])`` of request ``i``."""
        j = i % self.t.pool_ops
        vals = np.empty((1, 1 + self.words.shape[1]), np.uint32)
        vals[0, 0] = i
        vals[0, 1:] = self.words[j]
        return self.kind[j:j + 1], self.keys[j:j + 1], vals

    def arrival_s(self, i):
        """Arrival offset of request(s) ``i`` from the window's start."""
        P = self.t.pool_ops
        return (np.asarray(i) // P) * self.arrival[-1] + self.arrival[
            np.asarray(i) % P]

    def ops_made(self, n: int):
        """``(kind [n], kid [n], vals [n, Wv])`` of requests ``0..n-1``."""
        j = np.arange(n) % self.t.pool_ops
        vals = np.empty((n, 1 + self.words.shape[1]), np.uint32)
        vals[:, 0] = np.arange(n)
        vals[:, 1:] = self.words[j]
        return self.kind[j], self.kid[j], vals
