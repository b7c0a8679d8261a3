"""Pallas TPU kernels for the paper's hot paths, compiled under Mosaic on a
TPU and run in Pallas interpret mode elsewhere: h3_hash (GF(2) hashing),
xor_probe (fused decode+probe), xor_commit (masked store of engine-encoded
mutation records into every replica), xor_stream (fused whole-stream
probe->commit with the table VMEM-resident, swept in VMEM-sized passes past
the budget; a bucket-base offset runs shard-local partitions in the global
index space) and bulk_place (bulk-build placement).  All of them see the
lane-dense packed table of ``kernels.packed``.  Use repro.kernels.ops for
the jit'd, fallback-guarded entry points; the jnp oracles live in
repro.core.engine."""
from repro.kernels.ops import (h3_hash, stream_bucket_tiles, xor_commit,
                               xor_probe, xor_stream)

__all__ = ["h3_hash", "xor_probe", "xor_commit", "xor_stream",
           "stream_bucket_tiles"]
