"""Multi-device hash table: the paper's PE array across a device mesh.

8 simulated devices = 8 PEs; 4 own write ports (NSQ ratio 4/8); queries are
sharded across devices.  Two mappings (DESIGN.md §2.1):

  replicated    every device holds the whole table; mutations propagate with
                one ring all-gather per step (the FPGA inter-PE pipeline)
  bucket-sharded each device OWNS buckets/8 of the table; queries are routed
                to owner shards (all_to_all on the high H3 bits) and each
                partition streams locally — capacity scales with the mesh

Run:  PYTHONPATH=src python examples/distributed_hashtable.py
(the script re-execs itself with XLA_FLAGS for 8 host devices)
"""
import dataclasses
import os
import sys

if "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.execv(sys.executable, [sys.executable] + sys.argv)

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import HashTableConfig, OP_DELETE, OP_INSERT, OP_SEARCH
from repro.core.distributed import (init_distributed_table, make_ht_mesh,
                                    make_distributed_step,
                                    make_distributed_stream)


def main():
    n_dev = len(jax.devices())
    cfg = HashTableConfig(p=n_dev, k=n_dev // 2, buckets=1 << 12, slots=4,
                          replicate_reads=False, stagger_slots=True)
    mesh = make_ht_mesh(n_dev)
    table = init_distributed_table(cfg, jax.random.key(0))
    step = make_distributed_step(mesh, cfg)
    print(f"mesh: {n_dev} devices; NSQ-capable: first {cfg.k} "
          f"(ratio {cfg.k}/{cfg.p})")

    rng = np.random.default_rng(0)
    n_local = 32
    N = n_dev * n_local
    keys = rng.integers(1, 2 ** 32, size=(N, 1), dtype=np.uint32)
    vals = keys + 1

    # devices 0..3 insert their shard's keys; 4..7 are search-only
    ops = np.zeros(N, np.int32)
    ops[:cfg.k * n_local] = OP_INSERT
    table, res = step(table, jnp.array(ops), jnp.array(keys),
                      jnp.array(vals))
    print("inserted:", int(np.asarray(res.ok)[:cfg.k * n_local].sum()),
          "keys via", cfg.k, "write ports")

    # every device can search every key (replica reads are local!)
    table, res2 = step(table, jnp.full(N, OP_SEARCH, np.int32),
                       jnp.array(keys), jnp.array(vals))
    found = np.asarray(res2.found)
    print(f"visible on all devices after 1 step: "
          f"{int(found[:cfg.k * n_local].sum())}/{cfg.k * n_local}")

    # cross-device delete: device 1 deletes a key device 0 inserted
    ops3 = np.zeros(N, np.int32)
    ops3[n_local] = OP_DELETE
    k3 = keys.copy()
    k3[n_local] = keys[0]
    table, _ = step(table, jnp.array(ops3), jnp.array(k3), jnp.array(vals))
    table, res4 = step(table, jnp.full(N, OP_SEARCH, np.int32),
                       jnp.array(keys), jnp.array(vals))
    print("key deleted by another PE, now found:",
          bool(np.asarray(res4.found)[0]))

    # ---- bucket-sharded mapping: capacity scales with the mesh -------------
    scfg = dataclasses.replace(cfg, shards=n_dev)
    stab = init_distributed_table(scfg, jax.random.key(0), mesh)
    local_groups = stab.packed.sharding.shard_shape(stab.packed.shape)[1]
    print(f"\nsharded: {scfg.buckets} global buckets, each device owns "
          f"{scfg.local_buckets} ({local_groups} packed bucket groups) — "
          f"routed all_to_all stream, one launch for a whole [T, N] trace")
    stream = make_distributed_stream(mesh, scfg)
    T = 4
    n_ins = cfg.k * n_local                 # only NSQ-capable origins land
    sops = np.zeros((T, N), np.int32)
    sops[0] = OP_INSERT                     # step 0: every device inserts
    sops[1:] = OP_SEARCH                    # steps 1..: everyone searches
    skeys = np.broadcast_to(keys, (T, N, 1)).copy()
    svals = np.broadcast_to(vals, (T, N, 1)).copy()
    # steps 1+ search the keys that actually landed, from every origin device
    skeys[1:] = np.resize(keys[:n_ins], (N, 1))
    stab, sres = stream(stab, jnp.array(sops), jnp.array(skeys),
                        jnp.array(svals))
    f = np.asarray(sres.found)
    print(f"inserted {int(np.asarray(sres.ok)[0, :n_ins].sum())} keys via "
          f"owner routing; visible next step on every origin lane: "
          f"{int(f[1].sum())}/{N}")

    # ---- bounded two-pass router: routed width follows the measured load ---
    from repro.core import engine
    from repro.core.hashing import h3_hash
    bstream = make_distributed_stream(mesh, scfg, router="bounded")
    btab = init_distributed_table(scfg, jax.random.key(0), mesh)
    btab, bres = bstream(btab, jnp.array(sops), jnp.array(skeys),
                         jnp.array(svals))
    assert (np.asarray(bres.found) == f).all()      # bit-exact either router
    bucket = h3_hash(jnp.array(skeys.reshape(T * N, 1)),
                     btab.q_masks).reshape(T, N)
    plan = engine.plan_bounded_route(scfg, engine.shard_owner(scfg, bucket))
    print(f"bounded router (DESIGN.md §2.2): routed width "
          f"{plan.routed_width} vs skew-proof {plan.skewproof_width} "
          f"({plan.width_ratio:.2f}x), carry rate {plan.carry_rate:.2f}")


if __name__ == "__main__":
    main()
