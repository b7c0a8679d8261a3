"""Lane-dense layout of the XOR stores: the table's one stored format.

``XorHashTable.packed`` holds each replica's ``k`` partial stores as one
array ``[G, C, 128]``, the form every Pallas kernel works on.  Held in the
logical ``[k, B, S, W]`` shape, kernel blocks would pad the minor dims
``(S, W)`` to the TPU's (8, 128) tiles — 64x for ``W == 2``.  Instead:

  * bucket ``b`` is lane ``b % 128`` of bucket group ``g = b // 128``
    (``G = ceil(B / 128)``; lanes past ``B`` are padding, never addressed);
  * its ``C`` rows are ``row = plane * S * Wtot + word * S + slot`` with the
    words ordered key | value | valid (``Wtot = Wk + Wv + 1``), rounded up
    to a multiple of 8 sublanes.

A bucket-sharded table is stored per partition: each run of
``local_buckets`` buckets is packed on its own and the partitions follow
one another on the group axis, so one device's shard of the array is its
partition's packed table.  :func:`stored_column` maps a bucket to its
column (group and lane) in such an array, and :func:`columns` /
:func:`set_words` gather and scatter bucket columns: the jnp oracle, bulk
placement, resize migration and ``plaintext`` address the stored array
through them, touching only the buckets they read or write.
:func:`pack_stores` / :func:`unpack_stores` convert the whole array to and
from the logical ``[R, k, B, S, W]`` view, for tests and callers that want
it; no engine path converts.

A kernel reads one bucket as the lane column of its group block (a dynamic
index on the leading dim — the only dynamic vector index Mosaic lowers) and
writes one slot of one plane by a masked store into that block.  The
in-kernel helpers below keep queries on the lane axis: a step's gathered
columns are ``[C, n]``, the probe's per-lane results ``[1, n]`` rows.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

LANES = 128


def interpret_mode(interpret=None):
    """The kernels' ``interpret`` argument: an explicit value wins; None
    compiles under Mosaic on a TPU backend and runs the same kernel code in
    Pallas interpret mode everywhere else.  (Interpret mode discharges the
    kernel into XLA ops.  TPU interpret mode, ``pltpu.InterpretParams``,
    runs every ref access as a Python callback; under the mesh tests' 8
    fake CPU devices those callbacks serialize on one interpreter lock and
    can outlast XLA:CPU's 40 s collective rendezvous.)"""
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


def packed_rows(k: int, slots: int, words: int) -> int:
    """Rows ``C`` of a packed group block (``words == Wk + Wv + 1``)."""
    return -(-k * slots * words // 8) * 8


def packed_groups(buckets: int) -> int:
    return -(-buckets // LANES)


def pack(keys, vals, valid):
    """``[k, B, S, Wk]``, ``[k, B, S, Wv]``, ``[k, B, S]`` -> ``[G, C, 128]``."""
    k, B, S, _ = keys.shape
    words = jnp.concatenate([keys, vals, valid[..., None]], axis=-1)
    W = words.shape[-1]
    rows = jnp.transpose(words, (0, 3, 2, 1)).reshape(k * W * S, B)
    C, G = packed_rows(k, S, W), packed_groups(B)
    rows = jnp.pad(rows, ((0, C - k * W * S), (0, G * LANES - B)))
    return rows.reshape(C, G, LANES).transpose(1, 0, 2)


def unpack(packed, k: int, buckets: int, slots: int, key_words: int,
           val_words: int):
    """Inverse of :func:`pack`: ``(keys, vals, valid)`` store arrays."""
    G, C, _ = packed.shape
    W = key_words + val_words + 1
    rows = packed.transpose(1, 0, 2).reshape(C, G * LANES)
    words = rows[:k * W * slots, :buckets].reshape(k, W, slots, buckets)
    words = jnp.transpose(words, (0, 3, 2, 1))
    return (words[..., :key_words], words[..., key_words:W - 1],
            words[..., W - 1])


def stored_column(b, part_buckets: int):
    """Column ``group * 128 + lane`` of global bucket ``b`` (int32) in a
    stored array whose partitions hold ``part_buckets`` buckets each: the
    bucket itself when partitions fill whole groups, else shifted past the
    padding lanes of the partitions before it."""
    gap = packed_groups(part_buckets) * LANES - part_buckets
    return b if gap == 0 else b + b // part_buckets * gap


def entry_rows(plane, slot, slots: int, words: int):
    """``[n, words]`` rows of the words of slot ``slot [n]`` of plane
    ``plane`` (scalar or ``[n]``): ``plane * S * W + word * S + slot``."""
    base = plane * (slots * words) + slot
    return base[:, None] + slots * jnp.arange(words, dtype=jnp.int32)


def columns(tab, col, replica=None):
    """``[n, C]``: the columns ``col [n]`` of a stored array ``[R, G, C,
    128]``, each read from replica ``replica [n]`` (default 0)."""
    r = 0 if replica is None else replica
    return tab[r, col // LANES, :, col % LANES]


def planes(cols, k: int, slots: int, words: int):
    """Gathered columns ``[n, C]`` -> ``[n, k, S, W]`` plane/slot/word
    view."""
    n = cols.shape[0]
    return cols[:, :k * slots * words].reshape(n, k, words, slots
                                                ).transpose(0, 1, 3, 2)


def set_words(tab, col, rows, words):
    """``tab [..., G, C, 128]`` with ``words [n, W]`` stored at ``rows [n,
    W]`` of the columns ``col [n]``, in every leading index (every
    replica).  A column past the array (``>= G * 128``) is dropped."""
    return tab.at[..., (col // LANES)[:, None], rows,
                  (col % LANES)[:, None]].set(words, mode="drop")


def stored_buckets(tab, part_buckets: int) -> int:
    """Buckets a stored array ``[..., G, C, 128]`` holds when each of its
    partitions holds ``part_buckets``."""
    return tab.shape[-3] // packed_groups(part_buckets) * part_buckets


def pack_stores(keys, vals, valid, part_buckets: int):
    """``[R, k, B, S, W*]`` stores -> the stored ``[R, G, C, 128]`` array,
    each run of ``part_buckets`` buckets packed as its own partition."""
    R, k, B = keys.shape[:3]
    P = B // part_buckets

    def split(x):                   # [R, k, B, ...] -> [R * P, k, Bp, ...]
        x = x.reshape((R, k, P, part_buckets) + x.shape[3:])
        return jnp.moveaxis(x, 2, 1).reshape((R * P, k, part_buckets)
                                             + x.shape[4:])

    tab = jax.vmap(pack)(split(keys), split(vals), split(valid))
    return tab.reshape((R, P * tab.shape[1]) + tab.shape[2:])


def unpack_stores(tab, k: int, part_buckets: int, slots: int,
                  key_words: int, val_words: int):
    """Inverse of :func:`pack_stores`: ``(keys, vals, valid)`` with the
    replica axis, ``[R, k, B, S, W*]``."""
    R, G, C, _ = tab.shape
    Gp = packed_groups(part_buckets)
    P = G // Gp
    parts = jax.vmap(lambda t: unpack(t, k, part_buckets, slots, key_words,
                                      val_words))(
        tab.reshape(R * P, Gp, C, LANES))

    def merge(x):                   # [R * P, k, Bp, ...] -> [R, k, B, ...]
        x = jnp.moveaxis(x.reshape((R, P) + x.shape[1:]), 1, 2)
        return x.reshape((R, k, P * part_buckets) + x.shape[4:])

    return tuple(merge(x) for x in parts)


# ---------------------------------------------------------------------------
# In-kernel helpers (traced inside pallas kernels; values, not refs, unless
# named *_ref)
# ---------------------------------------------------------------------------

def i32(x):
    return jax.lax.bitcast_convert_type(x, jnp.int32)


def u32(x):
    return jax.lax.bitcast_convert_type(x, jnp.uint32)


def lane_ids(rows: int, n: int):
    return jax.lax.broadcasted_iota(jnp.int32, (rows, n), 1)


def pick_lane(x, i):
    """Column ``i`` of an int32 ``[R, n]`` value as ``[R, 1]``."""
    return jnp.sum(jnp.where(lane_ids(*x.shape) == i, x, 0), axis=1,
                   keepdims=True)


def pick_scalar(row, i):
    """Element ``i`` of an int32 ``[1, n]`` row as a scalar."""
    return jnp.sum(jnp.where(lane_ids(*row.shape) == i, row, 0))


def gather(tab_ref, bucket_at, lo, hi, n: int):
    """``[C, n]`` uint32 columns: column ``i`` for ``lo <= i < hi`` is the
    packed column of bucket ``bucket_at(i)`` (a scalar, relative to
    ``tab_ref``'s first group); the other columns are zero."""
    C = tab_ref.shape[1]
    lane = lane_ids(C, LANES)
    col = lane_ids(C, n)

    def body(i, acc):
        b = bucket_at(i)
        blk = i32(tab_ref[b // LANES])
        c = jnp.sum(jnp.where(lane == b % LANES, blk, 0), axis=1,
                    keepdims=True)
        return jnp.where(col == i, c, acc)

    return u32(jax.lax.fori_loop(lo, hi, body, jnp.zeros((C, n), jnp.int32)))


def _first(mask, slot_id, slots: int):
    """Index of the first True row per lane (0 when none — argmax)."""
    f = jnp.min(jnp.where(mask, slot_id, slots), axis=0, keepdims=True)
    return jnp.where(f == slots, 0, f)


def probe(cols, qkey, port, *, k: int, slots: int, key_words: int,
          val_words: int, stagger: bool):
    """The search dataflow on gathered columns (mirrors ``engine.probe_jnp``
    per lane).  ``cols [C, n]``, ``qkey [Wk, n]`` uint32, ``port [1, n]``
    int32.  Returns ``(found, mslot, oslot, hopen, value [Wv, n], rem
    [S*Wtot, n])`` with the flags/slots as ``[1, n]`` rows; ``rem`` is the
    non-search XOR basis (all planes but the lane's own port)."""
    S, Wk, Wv = slots, key_words, val_words
    SW = S * (Wk + Wv + 1)
    n = cols.shape[1]
    plane = lambda p: cols[p * SW:(p + 1) * SW]
    dec, own = plane(0), plane(0)
    for p in range(1, k):
        dec = dec ^ plane(p)
        own = jnp.where(port == p, plane(p), own)
    word = lambda x, w: x[w * S:(w + 1) * S]               # [S, n]
    key_eq = word(dec, 0) == qkey[0:1]
    for w in range(1, Wk):
        key_eq = key_eq & (word(dec, w) == qkey[w:w + 1])
    occ = (word(dec, Wk + Wv) & 1) == 1
    match = key_eq & occ
    slot_id = jax.lax.broadcasted_iota(jnp.int32, (S, n), 0)
    found = jnp.max(match.astype(jnp.int32), axis=0, keepdims=True) > 0
    mslot = _first(match, slot_id, S)
    opn = ~occ
    hopen = jnp.max(opn.astype(jnp.int32), axis=0, keepdims=True) > 0
    if stagger:                     # engine.staggered_open_slot, per lane
        op_i = opn.astype(jnp.int32)
        n_open = jnp.sum(op_i, axis=0, keepdims=True)
        rank = jnp.where(n_open > 0, port % jnp.maximum(n_open, 1), 0)
        csum = jnp.zeros((S, n), jnp.int32)
        acc = jnp.zeros((1, n), jnp.int32)
        for s in range(S):
            acc = acc + op_i[s:s + 1]
            csum = jnp.where(slot_id == s, acc, csum)
        oslot = _first(opn & (csum == rank + 1), slot_id, S)
    else:
        oslot = _first(opn, slot_id, S)
    value = jnp.concatenate(
        [jnp.sum(jnp.where(slot_id == mslot, i32(word(dec, Wk + w)), 0),
                 axis=0, keepdims=True) for w in range(Wv)], axis=0)
    value = jnp.where(found, u32(value), jnp.uint32(0))
    return found, mslot, oslot, hopen, value, dec ^ own


def select_slot(rem, slot, slots: int):
    """``rem [S*W, n]`` -> ``[W, n]``: each word at the lane's slot."""
    n = rem.shape[1]
    slot_id = jax.lax.broadcasted_iota(jnp.int32, (slots, n), 0)
    words = rem.shape[0] // slots
    return u32(jnp.concatenate(
        [jnp.sum(jnp.where(slot_id == slot,
                           i32(rem[w * slots:(w + 1) * slots]), 0),
                 axis=0, keepdims=True) for w in range(words)], axis=0))


def write_slot(blk_ref, lane, port, slot, enc, *, slots: int, words: int,
               enable=True):
    """Store one lane's encoded words into slot ``slot`` of plane ``port``
    of the bucket at lane ``lane`` of the group block ``blk_ref`` (``[C,
    128]``): a masked store of the block.  ``enc`` holds the ``words``
    int32 values (scalars or ``[1, 1]``); ``enable`` (scalar bool) masks
    the whole write."""
    C = blk_ref.shape[0]
    blk = blk_ref[...]
    row = jax.lax.broadcasted_iota(jnp.int32, (C, LANES), 0)
    base = port * (slots * words) + slot
    val = jnp.zeros((C, LANES), jnp.int32)
    hit = row < 0
    for w in range(words):
        m = row == base + w * slots
        val = jnp.where(m, enc[w], val)
        hit = hit | m
    hit = hit & (lane_ids(C, LANES) == lane) & enable
    blk_ref[...] = jnp.where(hit, u32(val), blk)
