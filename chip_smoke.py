#!/usr/bin/env python3
"""Bring-up smoke test: the hash-table server's main path on a TPU.

    python3 chip_smoke.py              # one chip: the hbm and resident phases
    python3 chip_smoke.py --chips 4    # the sharded table on a 2x2 mesh only

One chip.  The deployment is the paper's widest PE array with the
quickstart's 64-bit records: ``p=16, k=4, slots=4``, 2-word keys and values,
one read replica per chip, port-staggered slot choice.  Two phases:

  hbm       ``2**22`` buckets: one replica is 1.25 GiB, far past VMEM, so the
            fused stream kernel sweeps the table in VMEM-sized passes.
  resident  ``2**18`` buckets: the largest power of two whose replica the
            one-pass (VMEM-resident) layout compiles for on a v5e; at
            ``2**19`` the 160 MiB span exceeds the chip's 128 MiB of VMEM.

The resident phase runs first, so each phase's process memory peak is its
own.  Each phase bulk-loads distinct keys made from ``--seed`` to ~60% slot
occupancy with ``engine.bulk_build``, then serves a YCSB-B-like mix through
``TableServer`` on the fused stream kernel: 92% search and 4% update over the
loaded keys (zipfian, theta 0.99), 2% inserts of fresh keys, 2% deletes.
The same requests are then served by a second ``TableServer`` on the jnp
engine path (the scanned oracle) from the same bulk-built table; per-request
results and the final store arrays must match bit for bit.  A host check
holds the served results to the loaded data: every searched key that was
loaded, did not spill and is not mutated by the trace is found with its
loaded value.

``--chips 4`` runs only the sharded path and its oracle.  One ``2**22``-bucket
global table is bulk-loaded as above and laid out over the mesh twice: at
``shards=4`` and at ``shards=2, replica_groups=(3, 1)``, each served through
``make_distributed_stream`` with the bounded router.  The traffic is the same
zipfian mix, so hot keys load some shards more than others.  Each is
bit-exact against the ``shards=1`` replicated oracle on the same trace and
passes the host check, and its partitions must sit on four distinct devices.

Every phase prints what it ran; the last line of standard output is one JSON
object naming the device.  The script exits non-zero, printing no result,
when JAX finds no TPU (it never falls back to the CPU) or any check fails.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))

TABLE = dict(p=16, k=4, slots=4, key_words=2, val_words=2,
             replicate_reads=False, stagger_slots=True)
HBM_BUCKETS = 1 << 22
RESIDENT_BUCKETS = 1 << 18
LOAD_FACTOR = 0.6
SLAB_STEPS = 256
SLABS = 32
REQUEST_LANES = 1024
ZIPF_THETA = 0.99
# (search, update, insert of a fresh key, delete) fractions
MIX = (0.92, 0.04, 0.02, 0.02)
MESH_STEPS = 1024


class SmokeFailure(Exception):
    pass


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------------------
# Data, made from the seed
# ---------------------------------------------------------------------------

def distinct_keys(idx: np.ndarray, rng) -> np.ndarray:
    """``[n, 2]`` uint32 keys, distinct for distinct ``idx < 2**32``: word 0
    is an odd-multiplier bijection of the index, word 1 random."""
    w0 = (idx.astype(np.uint64) * np.uint64(0x9E3779B1)
          + np.uint64(0x7F4A7C15)) & np.uint64(0xFFFFFFFF)
    w1 = rng.integers(0, 2 ** 32, size=idx.shape, dtype=np.uint32)
    return np.stack([w0.astype(np.uint32), w1], axis=-1)


def zipf_ranks(n: int, size: int, rng) -> np.ndarray:
    """YCSB zipfian item draws over ``n`` items (rank 0 hottest), scrambled
    so the hot items are spread over the key space."""
    cdf = np.cumsum(1.0 / np.arange(1, n + 1, dtype=np.float64) ** ZIPF_THETA)
    ranks = np.searchsorted(cdf, rng.random(size) * cdf[-1])
    return rng.permutation(n)[np.minimum(ranks, n - 1)]


def ycsb_trace(n_loaded: int, n_ops: int, loaded_keys, rng):
    """The served mix.  Returns ops, keys, vals and, per op, the index of
    the loaded key it touches (-1 for fresh inserts)."""
    from repro.core import OP_DELETE, OP_INSERT, OP_SEARCH
    kind = rng.choice(4, size=n_ops, p=MIX)
    idx = zipf_ranks(n_loaded, n_ops, rng)
    fresh = kind == 2
    idx[fresh] = -1
    keys = loaded_keys[np.maximum(idx, 0)]
    keys[fresh] = distinct_keys(n_loaded + np.arange(int(fresh.sum())), rng)
    ops = np.array([OP_SEARCH, OP_INSERT, OP_INSERT, OP_DELETE],
                   np.int32)[kind]
    vals = rng.integers(1, 2 ** 32, size=(n_ops, 2), dtype=np.uint32)
    return ops, keys, vals, idx


# ---------------------------------------------------------------------------
# One chip: bulk load, then TableServer on the fused kernel vs the oracle
# ---------------------------------------------------------------------------

def compile_stream(table, backend: str, steps: int):
    """AOT-compile ``engine.run_stream`` for one slab shape; returns the
    executable and its compile seconds."""
    import jax
    import jax.numpy as jnp
    from repro.core import engine
    cfg = table.cfg
    N = cfg.queries_per_step
    shapes = (jax.ShapeDtypeStruct((steps, N), jnp.int32),
              jax.ShapeDtypeStruct((steps, N, cfg.key_words), jnp.uint32),
              jax.ShapeDtypeStruct((steps, N, cfg.val_words), jnp.uint32))
    t0 = time.perf_counter()
    fn = jax.jit(functools.partial(engine.run_stream, backend=backend))
    compiled = fn.lower(table, *shapes).compile()
    return compiled, time.perf_counter() - t0


def serve(table, stream, requests, steps: int):
    from repro.serving.engine import ServeConfig, TableServer
    srv = TableServer(table.cfg, table, stream,
                      ServeConfig(slab_steps=steps, geometry_replan=False))
    reqs = [srv.submit(o, k, v) for o, k, v in requests]
    srv.run()
    return srv, reqs


def tables_equal(a, b) -> bool:
    import jax.numpy as jnp
    return bool(jnp.array_equal(a.packed, b.packed))


def run_phase(name: str, buckets: int, slabs: int, steps: int, seed: int,
              device) -> None:
    import jax
    import jax.numpy as jnp
    from repro.core import HashTableConfig, OP_SEARCH, engine, init_table
    from repro.kernels import ops as kops

    cfg = HashTableConfig(buckets=buckets, **TABLE)
    rng = np.random.default_rng(seed)
    n_load = int(LOAD_FACTOR * buckets * cfg.slots)
    keys = distinct_keys(np.arange(n_load), rng)
    vals = rng.integers(1, 2 ** 32, size=(n_load, 2), dtype=np.uint32)

    backend = engine._resolve_name(cfg)
    check(backend == "pallas", f"[{name}] backend resolved to {backend!r}, "
                               f"not pallas")
    table = init_table(cfg, jax.random.key(seed))
    t0 = time.perf_counter()
    table, report = engine.bulk_build(table, jnp.asarray(keys),
                                      jnp.asarray(vals))
    jax.block_until_ready(table)
    spilled = np.asarray(report.spilled)
    log(f"[{name}] bulk_build: {n_load} distinct keys into {buckets} buckets "
        f"({int(spilled.sum())} spilled), {time.perf_counter() - t0:.3f} s "
        f"including compile")

    passes = kops.stream_bucket_tiles(table.packed)
    layout = ("resident (one VMEM pass)" if passes == 1
              else f"swept in {passes} VMEM passes")
    fused, fused_s = compile_stream(table, "pallas", steps)
    oracle, oracle_s = compile_stream(table, "jnp", steps)
    custom = "tpu_custom_call" in fused.as_text()
    log(f"[{name}] served path: engine.run_stream backend={backend} "
        f"kernel=xor_stream layout={layout} "
        f"table_vmem_bytes={kops.table_vmem_bytes(table.packed)} "
        f"tpu_custom_call={custom}")
    check(custom, f"[{name}] no tpu_custom_call in the served stream's HLO")
    log(f"[{name}] compile_s fused={fused_s:.3f} jnp_oracle={oracle_s:.3f}")

    n_ops = slabs * steps * cfg.queries_per_step
    ops, qkeys, qvals, idx = ycsb_trace(n_load, n_ops, keys, rng)
    requests = [(ops[i:i + REQUEST_LANES], qkeys[i:i + REQUEST_LANES],
                 qvals[i:i + REQUEST_LANES])
                for i in range(0, n_ops, REQUEST_LANES)]
    srv, got = serve(table, fused, requests, steps)
    log(f"[{name}] TableServer: {srv.live_lanes} operations served in "
        f"{srv.slabs} slabs of [{steps}, {cfg.queries_per_step}] "
        f"(op counts search/update+insert/delete = "
        f"{int((ops == OP_SEARCH).sum())}/{int((ops == 2).sum())}/"
        f"{int((ops == 3).sum())})")
    check(srv.live_lanes == n_ops, f"[{name}] served {srv.live_lanes} of "
                                   f"{n_ops} operations")

    ref, want = serve(table, oracle, requests, steps)
    for a, b in zip(got, want):
        for f in ("found", "ok", "value"):
            check(np.array_equal(getattr(a, f), getattr(b, f)),
                  f"[{name}] request {a.rid}: {f} differs from the jnp "
                  f"oracle")
    check(tables_equal(srv.table, ref.table),
          f"[{name}] final stores differ from the jnp oracle")
    log(f"[{name}] oracle: per-request results and final stores bit-exact "
        f"with the jnp engine path")

    host_check(name, ops, idx, spilled, vals,
               np.concatenate([r.found for r in got]),
               np.concatenate([r.value for r in got]))

    # the process peak: phases run smallest table first, so each phase's
    # figure is its own
    stats = device.memory_stats() or {}
    log(f"[{name}] process peak_bytes_in_use="
        f"{stats.get('peak_bytes_in_use')} cfg.table_bytes={cfg.table_bytes}")


def host_check(name, ops, idx, spilled, vals, found, value) -> None:
    """Every search of a loaded key that did not spill and that the trace
    never mutates is found with its loaded value."""
    from repro.core import OP_SEARCH
    mutated = np.zeros(len(vals), bool)
    mutated[idx[(ops != OP_SEARCH) & (idx >= 0)]] = True
    probe = (ops == OP_SEARCH) & ~mutated[idx] & ~spilled[idx]
    check(found[probe].all(), f"[{name}] {int((~found[probe]).sum())} loaded "
                              f"keys not found")
    check(np.array_equal(value[probe], vals[idx[probe]]),
          f"[{name}] loaded keys found with wrong values")
    log(f"[{name}] host check: {int(probe.sum())} searches of loaded, "
        f"unmutated keys found with their loaded values")


# ---------------------------------------------------------------------------
# Four chips: the sharded table vs the shards=1 oracle
# ---------------------------------------------------------------------------

def load_oracle_table(cfg, seed: int):
    """The ``shards=1`` table, bulk-loaded like the one-chip phases, and what
    the host check needs to know about the load."""
    import jax
    import jax.numpy as jnp
    from repro.core import engine, init_table
    rng = np.random.default_rng(seed)
    n_load = int(LOAD_FACTOR * cfg.buckets * cfg.slots)
    keys = distinct_keys(np.arange(n_load), rng)
    vals = rng.integers(1, 2 ** 32, size=(n_load, 2), dtype=np.uint32)
    table, report = engine.bulk_build(init_table(cfg, jax.random.key(seed)),
                                      jnp.asarray(keys), jnp.asarray(vals))
    spilled = np.asarray(report.spilled)
    log(f"[mesh] bulk_build: {n_load} distinct keys into {cfg.buckets} "
        f"global buckets ({int(spilled.sum())} spilled)")
    return table, keys, vals, spilled, rng


def shard_table(cfg, mesh, table):
    """Lay the ``shards=1`` table's bucket partitions over ``mesh`` as
    ``cfg``'s global table: device ``d`` holds shard ``shard_of[d]``'s
    partition, the layout ``init_distributed_table`` allocates."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import XorHashTable, engine
    shard_of = engine.replica_layout(cfg)[0]
    lb = cfg.local_buckets
    lay = lambda x: jnp.concatenate(
        [x[:, :, s * lb:(s + 1) * lb] for s in shard_of], axis=2)
    q_masks = jax.device_put(table.q_masks, NamedSharding(mesh, P()))
    laid = XorHashTable.from_stores(q_masks, *map(lay, table.stores()), cfg)
    return XorHashTable(q_masks, jax.device_put(
        laid.packed, NamedSharding(mesh, P(None, "ht"))), cfg)


def run_sharded(name: str, cfg, oracle, trace) -> None:
    import jax
    import jax.numpy as jnp
    from repro.core import distributed as dist, engine

    table_1, (tr, rr), (ops_h, idx, spilled, vals_h) = oracle
    ops, keys, vals = trace
    Dv = cfg.mesh_devices
    mesh = dist.make_ht_mesh(Dv)
    table = shard_table(cfg, mesh, table_1)
    from repro.kernels.packed import packed_groups
    devs = {s.device for s in table.packed.addressable_shards}
    check(len(devs) == Dv, f"[{name}] partitions on {len(devs)} devices, "
                           f"want {Dv}")
    lb = cfg.local_buckets
    check(all(s.data.shape[1] == packed_groups(lb)
              for s in table.packed.addressable_shards),
          f"[{name}] a device holds other than its {lb}-bucket partition")
    load = np.bincount(np.asarray(engine.shard_owner(cfg, rr.bucket)).ravel(),
                       minlength=cfg.shards)
    log(f"[{name}] {cfg.buckets} global buckets, shards={cfg.shards} "
        f"replica_groups={cfg.replica_groups} router={cfg.router}: "
        f"{lb}-bucket partitions on {len(devs)} devices "
        f"({sorted(d.id for d in devs)}); operations per shard "
        f"{load.tolist()}")

    stream = dist.make_distributed_stream(mesh, cfg)
    t0 = time.perf_counter()
    ts, rs = stream(table, ops, keys, vals)
    jax.block_until_ready(rs)
    log(f"[{name}] sharded stream: {ops.size} operations in "
        f"[{ops.shape[0]}, {ops.shape[1]}], "
        f"{time.perf_counter() - t0:.3f} s including compile")

    for f in ("found", "value", "ok", "bucket"):
        check(np.array_equal(np.asarray(getattr(rs, f)),
                             np.asarray(getattr(rr, f))),
              f"[{name}] results.{f} differ from the shards=1 oracle")
    shard_of = np.asarray(engine.replica_layout(cfg)[0])
    for f, a, b in zip(("keys", "vals", "valid"), ts.stores(), tr.stores()):
        for d in range(Dv):
            s = int(shard_of[d])
            check(bool(jnp.array_equal(a[:, :, d * lb:(d + 1) * lb],
                                       b[:, :, s * lb:(s + 1) * lb])),
                  f"[{name}] device {d}'s {f} differ from the oracle's "
                  f"shard {s}")
    log(f"[{name}] oracle: results and every device's partition bit-exact "
        f"with the shards=1 replicated oracle")
    host_check(name, ops_h, idx, spilled, vals_h,
               np.asarray(rs.found).ravel(),
               np.asarray(rs.value).reshape(-1, cfg.val_words))


def run_mesh(buckets: int, steps: int, seed: int) -> None:
    """Bulk-load once, run the ``shards=1`` oracle once, then check every
    sharded layout against it on the same trace."""
    import jax
    import jax.numpy as jnp
    from repro.core import distributed as dist
    configs = mesh_configs(buckets)
    cfg_rep = dataclasses.replace(configs[0][1], shards=1,
                                  replica_groups=None, router="skewproof")
    table, keys_l, vals_l, spilled, rng = load_oracle_table(cfg_rep, seed)
    N = cfg_rep.queries_per_step
    ops, keys, vals, idx = ycsb_trace(len(keys_l), steps * N, keys_l, rng)
    trace = (jnp.asarray(ops.reshape(steps, N)),
             jnp.asarray(keys.reshape(steps, N, cfg_rep.key_words)),
             jnp.asarray(vals.reshape(steps, N, cfg_rep.val_words)))
    mesh = dist.make_ht_mesh(configs[0][1].mesh_devices)
    t0 = time.perf_counter()
    tr, rr = dist.make_distributed_stream(mesh, cfg_rep)(table, *trace)
    jax.block_until_ready(rr)
    log(f"[mesh] shards=1 oracle: {ops.size} operations, "
        f"{time.perf_counter() - t0:.3f} s including compile")
    oracle = (table, (tr, rr), (ops, idx, spilled, vals_l))
    for name, cfg in configs:
        run_sharded(name, cfg, oracle, trace)


def mesh_configs(buckets: int):
    from repro.core import HashTableConfig
    base = HashTableConfig(buckets=buckets, router="bounded", **TABLE)
    return [("shards4_bounded", dataclasses.replace(base, shards=4)),
            ("shards2_groups31", dataclasses.replace(
                base, shards=2, replica_groups=(3, 1)))]


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded mesh phases")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    src = os.path.join(REPO, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke.py: no repro package under {src}; run it from a "
              f"checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke.py: no TPU found (JAX platform "
              f"{dev.platform!r}); this check runs only on the chip",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke.py: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    log(f"device: {dev.device_kind} x{len(devices)}, jax {jax.__version__}, "
        f"compile cache {cache}")
    try:
        if args.chips == 4:
            run_mesh(HBM_BUCKETS, MESH_STEPS, args.seed)
        else:
            run_phase("resident", RESIDENT_BUCKETS, SLABS, SLAB_STEPS,
                      args.seed, dev)
            run_phase("hbm", HBM_BUCKETS, SLABS, SLAB_STEPS, args.seed, dev)
    except SmokeFailure as e:
        print(f"chip_smoke.py: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
