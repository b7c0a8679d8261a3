"""Hypothesis property test for the fused stream kernel's commit conflicts:
on arbitrary S/I/U/D traces over a TINY key space (heavy same-step duplicate
(bucket, slot) write targets, same-port and cross-port, inserts racing
deletes), the fused kernel stays bit-exact with the scanned jnp oracle on
the one-pass layout and on 2- and 4-pass sweeps (a shrunken VMEM budget).
Guarded on hypothesis like tests/test_hash_table_property.py."""
import functools

import numpy as np
import pytest
import jax
import jax.numpy as jnp

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

import repro.kernels.ops as kops  # noqa: E402
from repro.core import (HashTableConfig, OP_DELETE, OP_INSERT,  # noqa: E402
                        OP_SEARCH, init_table, run_stream, schedule_queries)
from test_stream_fused import _assert_same  # noqa: E402

N_QUERIES = 48          # fixed -> one trace shape, one compile per layout
BUCKETS = 512           # four 128-bucket groups: a 4-pass sweep is real
KEYS = st.integers(0, 9)      # index into the tiny colliding key pool
# sweep passes -> the buckets (one per pass) the key pool crowds, and how
# many pool keys share each: 3 keys over 2 slots overflow a bucket
POOL = ((0, 2), (1, 3), (2, 2), (3, 3))


@functools.lru_cache(maxsize=1)
def _key_pool():
    """Ten one-word keys crowded into four buckets, one bucket in each
    quarter of the table — the four passes of the 4-pass sweep — so every
    layout sees same-bucket collisions between distinct keys and lanes of
    one step spread over every pass."""
    from repro.core.hashing import h3_hash as h3
    tab = init_table(HashTableConfig(p=2, k=2, buckets=BUCKETS, slots=2,
                                     queries_per_pe=2), jax.random.key(0))
    cand = np.arange(1, 1 << 16, dtype=np.uint32)
    b = np.asarray(h3(jnp.asarray(cand[:, None]), tab.q_masks))
    pool = []
    for quarter, n in POOL:
        lo = quarter * BUCKETS // 4
        in_q = b[(b >= lo) & (b < lo + BUCKETS // 4)]
        target = np.bincount(in_q).argmax()         # the most crowded bucket
        pool += [int(x) for x in cand[b == target][:n]]
    return np.asarray(pool, np.uint32), np.asarray(tab.q_masks)


@st.composite
def traces(draw):
    ops, keys, vals = [], [], []
    for _ in range(N_QUERIES):
        ops.append(draw(st.sampled_from([OP_SEARCH, OP_INSERT, OP_INSERT,
                                         OP_DELETE])))
        keys.append(draw(KEYS))
        vals.append(draw(st.integers(1, 2 ** 31)))
    return ops, keys, vals


@settings(max_examples=12, deadline=None)
@given(trace=traces(), stagger=st.booleans())
def test_fused_layouts_match_oracle_on_duplicate_heavy_traces(trace, stagger):
    # qpp=2 puts two lanes on every port per step: same-port duplicates;
    # stagger=False lets distinct ports pick the same open slot: cross-port
    # duplicates.  The key pool crowds distinct keys into shared buckets.
    cfg = HashTableConfig(p=2, k=2, buckets=BUCKETS, slots=2,
                          queries_per_pe=2, stagger_slots=stagger)
    pool, masks = _key_pool()
    op, key, val = trace
    keys = pool[np.asarray(key)].reshape(-1, 1)
    vals = np.asarray(val, np.uint32).reshape(-1, 1)
    ops, kk, vv = schedule_queries(np.asarray(op, np.int32), keys, vals, cfg)
    tab = init_table(cfg, jax.random.key(0))
    assert (np.asarray(tab.q_masks) == masks).all()
    args = (tab, jnp.array(ops), jnp.array(kk), jnp.array(vv))
    tab_j, res_j = run_stream(*args, backend="jnp", fused=False)
    saved = kops.VMEM_TABLE_BUDGET_BYTES
    try:
        for passes in (1, 2, 4):
            kops.VMEM_TABLE_BUDGET_BYTES = \
                kops.table_vmem_bytes(tab.packed) // passes
            assert kops.stream_bucket_tiles(tab.packed) == passes
            tab_f, res_f = run_stream(*args, fused=True)
            _assert_same(tab_j, res_j, tab_f, res_f,
                         f"{passes}-pass stagger={stagger}")
    finally:
        kops.VMEM_TABLE_BUDGET_BYTES = saved
