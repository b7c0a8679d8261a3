"""Compile every Pallas kernel of the served and bulk paths for a TPU v5e,
without a chip: the TPU compiler is installed, so lowering for a described
``v5e:2x2`` topology raises what Mosaic would raise on the device (tiling
rules, memory spaces, VMEM limits).  Shapes are chip_smoke.py's widths:
``p=16`` lanes, ``k=4`` planes, 4 slots, 2-word keys and values, 256-step
slabs; the resident stream at ``2**18`` buckets (the largest one-pass size)
and the swept stream at ``2**22``.  Each test asserts the kernel reached the
compiled program as a ``tpu_custom_call``.

The topology is described inside a module fixture (never at import): only
one process may load the TPU library, and only the worker given this file
needs it.
"""
from __future__ import annotations

import functools
import re

import pytest
import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops as kops
from repro.kernels.bulk_place import bulk_place_pallas
from repro.kernels.h3_hash import h3_hash_pallas
from repro.kernels.xor_commit import xor_commit_pallas
from repro.kernels.xor_probe import xor_probe_pallas
from repro.kernels.xor_stream import xor_stream_pallas

K, S, WK, WV, N, T = 4, 4, 2, 2, 16, 256


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype=jnp.uint32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)


def _compiled_hlo(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _store(spec, buckets):
    return (spec((K, buckets, S, WK)), spec((K, buckets, S, WV)),
            spec((K, buckets, S)))


def test_h3_hash_compiles_for_v5e(spec):
    hlo = _compiled_hlo(
        functools.partial(h3_hash_pallas, block_n=1024, interpret=False),
        spec((WK, T * N)), spec((22, WK)))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("buckets,passes", [(1 << 18, 1), (1 << 22, 16)],
                         ids=["resident", "blocked"])
def test_xor_stream_compiles_for_v5e(spec, buckets, passes):
    def stream(bucket, ops, qk, qv, sk, sv, sb):
        port = jnp.arange(N, dtype=jnp.int32) % K
        return xor_stream_pallas(bucket, port, jnp.ones((N,), jnp.int32),
                                 ops, qk, qv, sk, sv, sb,
                                 passes=passes, stagger=True,
                                 interpret=False,
                                 vmem_limit=kops.VMEM_LIMIT_BYTES)
    hlo = _compiled_hlo(stream, spec((T, N)), spec((T, N), jnp.int32),
                        spec((T, N, WK)), spec((T, N, WV)),
                        *_store(spec, buckets))
    assert "tpu_custom_call" in hlo
    # the kernel's stable name, which the device trace's op events carry,
    # and the seam's scopes around it
    assert re.search(r"%xor_stream\.\d+ = .*custom_call_target="
                     r"\"tpu_custom_call\"", hlo)
    for scope in ("kv.prepass", "kv.table_pack", "kv.table_unpack",
                  "kv.unpermute"):
        assert f"/{scope}/" in hlo, scope


def test_bulk_place_compiles_for_v5e(spec):
    n, buckets = 4096, 1 << 14
    hlo = _compiled_hlo(
        functools.partial(bulk_place_pallas, interpret=False),
        spec((n,), jnp.int32), spec((n,), jnp.int32), spec((n, WK)),
        spec((n, WV)), spec((buckets, S, WK)), spec((buckets, S, WV)),
        spec((buckets, S)))
    assert "tpu_custom_call" in hlo


def test_xor_probe_compiles_for_v5e(spec):
    hlo = _compiled_hlo(
        functools.partial(xor_probe_pallas, stagger=True, interpret=False,
                          vmem_limit=kops.VMEM_LIMIT_BYTES),
        spec((N,)), spec((N,), jnp.int32), spec((N, WK)),
        *_store(spec, 1 << 14))
    assert "tpu_custom_call" in hlo


def test_xor_commit_compiles_for_v5e(spec):
    sk, sv, sb = _store(spec, 1 << 14)
    rep = lambda x: spec((1,) + x.shape)
    hlo = _compiled_hlo(
        functools.partial(xor_commit_pallas, interpret=False,
                          vmem_limit=kops.VMEM_LIMIT_BYTES),
        rep(sk), rep(sv), rep(sb), spec((N,), jnp.int32),
        spec((N,), jnp.int32), spec((N,), jnp.int32), spec((N, WK)),
        spec((N, WV)), spec((N,)))
    assert "tpu_custom_call" in hlo
