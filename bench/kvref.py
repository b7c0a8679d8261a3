"""The plain reference of the table's semantics, and the comparison that
decides ``correct``.

It imports nothing of the program.  From the loaded records, the H3 matrix,
the ops the run made and the slabs the server dispatched, it works out with
numpy what every op must answer and what the table must hold afterwards:

* hash: bucket bit ``j`` is the parity of ``key & q_masks[j]`` over the key
  words (H3);
* bulk load: records go in load order; the first ``slots`` distinct keys of
  a bucket are stored, the rest spill and are absent;
* steps: the lanes of one ``[N]`` step of a slab read the table as it stood
  before the step.  A search answers ``found`` and the stored value; an
  update (insert of a key) answers the same and is acknowledged exactly
  where its key is present: the server has to put every update on a lane
  whose PE owns a write port (PEs ``< k``), so an update of a present key
  that is refused is a fault of the server;
* lanes and PEs (:func:`lane_pe`): on one memory domain lane ``n`` of an
  ``[N]`` step belongs to PE ``n % p``; on a mesh of ``devices`` origin
  devices the step's lanes are split into ``devices`` contiguous blocks of
  ``N / devices`` and lane ``n`` belongs to its origin device, PE
  ``n // (N / devices)``;
* write ports: PE ``j`` writes XOR plane ``j``, encoded against the pre-step
  planes.  Updates of one record from ``m`` ports in one step therefore
  leave the XOR of their values, and of the old value when ``m`` is even
  (one port: last lane wins); the key stays present;
* every acknowledged update is visible to every later step.

The traffic only reads and updates loaded keys, so no record is added or
removed after the load.  The generator loads only keys that have room in
their bucket (``kvtraffic.make_loaded``), so nothing spills; were a key to
spill, its bucket would stay full and its updates be refused.

``compare`` holds the client-visible answers and the final table to that,
op by op; each count it returns must be 0.
"""
from __future__ import annotations

import numpy as np

OP_SEARCH, OP_UPDATE = 1, 2


def h3(keys: np.ndarray, q_masks: np.ndarray) -> np.ndarray:
    """Bucket index of each ``[n, W]`` uint32 key under ``q_masks [J, W]``.

    Parity is linear under XOR, so bit ``j`` is the XOR over the key's bytes
    of the parity of ``byte & mask-byte``: one 256-entry table per byte
    position holds every bit's share."""
    byte = np.arange(256, dtype=np.uint32)
    bucket = np.zeros(len(keys), np.int64)
    for w in range(keys.shape[1]):
        for shift in range(0, 32, 8):
            table = np.zeros(256, np.int64)
            for j, row in enumerate(q_masks):
                table |= (np.bitwise_count((byte << shift) & row[w])
                          & 1).astype(np.int64) << j
            bucket ^= table[(keys[:, w] >> shift) & 0xFF]
    return bucket


def bulk_load(keys: np.ndarray, q_masks: np.ndarray, slots: int
              ) -> np.ndarray:
    """Which of the distinct ``keys`` (load order) the table holds."""
    bucket = h3(keys, q_masks)
    order = np.argsort(bucket * len(keys) + np.arange(len(keys)))
    b = bucket[order]
    first = np.r_[0, np.flatnonzero(b[1:] != b[:-1]) + 1]
    start = np.repeat(first, np.diff(np.r_[first, len(b)]))
    present = np.empty(len(keys), bool)
    present[order] = (np.arange(len(b)) - start) < slots
    return present


def pack64(words: np.ndarray) -> np.ndarray:
    """``[..., 2]`` uint32 words -> uint64 (word 0 low)."""
    w = words.astype(np.uint64)
    return w[..., 0] | (w[..., 1] << np.uint64(32))


def lane_pe(lanes: int, p: int, devices: int = 1) -> np.ndarray:
    """The PE of each of a step's ``lanes`` lanes: ``n % p`` on one memory
    domain (``devices == 1``), the origin device ``n // (lanes / devices)``
    on a mesh of ``devices`` devices."""
    n = np.arange(lanes, dtype=np.int64)
    return n % p if devices == 1 else n // (lanes // devices)


def compare(*, p: int, k: int, slots: int, loaded_keys, loaded_vals,
            q_masks, kind, kid, op_vals, answered, found, ok, value,
            slab_ops, slab_keys, slab_vals, final_keys, final_vals,
            final_valid, devices: int = 1):
    """Counts of disagreement with the reference (each must be 0), and a
    few facts about the run.

    ``kind/kid/op_vals`` describe the ``n`` ops submitted (op index =
    position); ``answered/found/ok/value`` are what the clients got back;
    ``slab_*`` are the ``[S, T, N, ...]`` slabs the server dispatched, in
    order; ``final_*`` is the table's plaintext after the run
    (``[B, slots, W]`` records and their valid bits).  ``devices`` is the
    number of origin devices the steps' lanes are split over: 1 for one
    memory domain, whose lane ``n`` belongs to PE ``n % p``; on a mesh,
    lane ``n`` belongs to PE ``n // (N / devices)``, its origin device
    (:func:`lane_pe`)."""
    n = len(kind)
    present = bulk_load(loaded_keys, q_masks, slots)

    # -- where each op ran: its tag (value word 0) in the dispatched slabs --
    S, T, N = slab_ops.shape
    live = slab_ops != 0
    s_idx, t_idx, lane = np.nonzero(live)
    tag = slab_vals[s_idx, t_idx, lane, 0].astype(np.int64)
    known = tag < n
    seen = np.bincount(tag[known], minlength=n)
    altered = int((~known).sum())
    tag_k = tag[known]
    same = ((slab_ops[s_idx, t_idx, lane][known] == kind[tag_k])
            & (pack64(slab_keys[s_idx, t_idx, lane][known])
               == pack64(loaded_keys[kid[tag_k]]))
            & (pack64(slab_vals[s_idx, t_idx, lane][known])
               == pack64(op_vals[tag_k])))
    altered += int((~same).sum())
    lost = int(((seen == 0) | ~answered).sum())
    dup = int((seen > 1).sum())
    step = np.full(n, -1, np.int64)
    step[tag_k] = (s_idx * T + t_idx)[known]
    op_lane = np.zeros(n, np.int64)
    op_lane[tag_k] = lane[known]
    ran = seen == 1

    # -- accepted writes, grouped per (key, step) --------------------------
    pe = lane_pe(N, p, devices)[op_lane]
    is_upd = kind == OP_UPDATE
    writes = ran & is_upd & present[kid] & (pe < k)
    w_idx = np.flatnonzero(writes)
    w_key = kid[w_idx].astype(np.int64)
    w_step = step[w_idx]
    w_port = np.minimum(pe[w_idx], k - 1)
    # one port: the last lane of the step wins
    o = np.lexsort((op_lane[w_idx], w_port, w_step, w_key))
    w_idx, w_key, w_step, w_port = w_idx[o], w_key[o], w_step[o], w_port[o]
    last = np.r_[(w_key[1:] != w_key[:-1]) | (w_step[1:] != w_step[:-1])
                 | (w_port[1:] != w_port[:-1]), True][:len(w_idx)]
    w_idx, w_key, w_step = w_idx[last], w_key[last], w_step[last]
    new = pack64(op_vals[w_idx])
    g_new = np.r_[True, (w_key[1:] != w_key[:-1])
                  | (w_step[1:] != w_step[:-1])][:len(w_key)]
    g_id = np.cumsum(g_new) - 1
    g_key, g_step = w_key[g_new], w_step[g_new]
    m = np.bincount(g_id)
    x = np.bitwise_xor.reduceat(new, np.flatnonzero(g_new)) if len(new) \
        else np.zeros(0, np.uint64)
    # value after group g: x_g, XOR the value before it when m_g is even
    G = len(g_key)
    odd = (m % 2) == 1
    key_first = np.r_[True, g_key[1:] != g_key[:-1]] if G else np.zeros(
        0, bool)
    starts = np.maximum.accumulate(np.where(odd | key_first,
                                            np.arange(G), -1)) if G else \
        np.zeros(0, np.int64)
    pref = np.bitwise_xor.accumulate(x) if G else x
    before = np.where(starts > 0, pref[np.maximum(starts - 1, 0)],
                      np.uint64(0))
    load64 = pack64(loaded_vals)
    after = pref ^ before ^ np.where(odd[starts] if G else odd,
                                     np.uint64(0), load64[g_key])

    # -- what each op must answer: the value before its step ---------------
    span = np.int64(S * T + 1)
    g_code = g_key * span + g_step
    q_code = kid.astype(np.int64) * span + np.maximum(step, 0)
    at = np.searchsorted(g_code, q_code, side="left") - 1
    hit = (at >= 0) & (g_key[np.maximum(at, 0)] == kid) if G else \
        np.zeros(n, bool)
    ref_val = np.where(hit, after[np.maximum(at, 0)] if G else 0,
                       load64[kid])
    ref_found = present[kid]
    ref_val = np.where(ref_found, ref_val, np.uint64(0))
    ref_ok = np.where(is_upd, ref_found, True)
    judged = ran & answered

    # -- the table afterwards ---------------------------------------------
    fin = np.zeros(len(loaded_keys), np.uint64)
    fin[:] = load64
    if G:
        last_g = np.r_[g_key[1:] != g_key[:-1], True]
        fin[g_key[last_g]] = after[last_g]
    ref_keys = pack64(loaded_keys[present])
    ref_vals = fin[present]
    live_rec = (final_valid & 1).astype(bool)
    got_keys = pack64(final_keys[live_rec])
    got_vals = pack64(final_vals[live_rec])
    ro, go = np.argsort(ref_keys), np.argsort(got_keys)
    ref_keys, ref_vals = ref_keys[ro], ref_vals[ro]
    got_keys, got_vals = got_keys[go], got_vals[go]
    common, ri, gi = np.intersect1d(ref_keys, got_keys, assume_unique=False,
                                    return_indices=True)
    state_bad = (len(ref_keys) - len(common)) + (len(got_keys) - len(common))
    state_bad += int((ref_vals[ri] != got_vals[gi]).sum())

    checks = {
        "lost_ops": lost,
        "duplicated_ops": dup,
        "altered_ops": altered,
        "found_mismatch": int((found[judged] != ref_found[judged]).sum()),
        "ok_mismatch": int((ok[judged] != ref_ok[judged]).sum()),
        "value_mismatch": int((pack64(value)[judged]
                               != ref_val[judged]).sum()),
        "state_mismatch": int(state_bad),
    }
    facts = {
        "ops": n,
        "records": int(present.sum()),
        "spilled": int((~present).sum()),
        "xor_merged_updates": int(m[m > 1].sum()) if G else 0,
    }
    return checks, facts
