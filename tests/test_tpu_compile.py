"""Compile every Pallas kernel of the served and bulk paths for a TPU v5e,
without a chip: the TPU compiler is installed, so lowering for a described
``v5e:2x2`` topology raises what Mosaic would raise on the device (tiling
rules, memory spaces, VMEM limits).  Shapes are chip_smoke.py's widths:
``p=16`` lanes, ``k=4`` planes, 4 slots, 2-word keys and values, 256-step
slabs; the resident stream at ``2**18`` buckets (the largest one-pass size)
and the swept stream at ``2**22``.  Each test asserts the kernel reached the
compiled program as a ``tpu_custom_call``; the served stream is compiled
whole (``engine.run_stream`` with the table donated) and must move the
stored table only through the kernel.

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

from repro.core import HashTableConfig, XorHashTable, engine
from repro.core.hash_table import packed_shape
from repro.kernels import ops as kops
from repro.kernels import packed as pk
from repro.kernels.bulk_place import bulk_place_pallas
from repro.kernels.h3_hash import h3_hash_pallas
from repro.kernels.xor_commit import xor_commit_pallas
from repro.kernels.xor_probe import xor_probe_pallas

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


def _cfg(buckets):
    return HashTableConfig(p=N, k=K, buckets=buckets, slots=S, key_words=WK,
                           val_words=WV, replicate_reads=False,
                           stagger_slots=True, backend="pallas")


def _table(spec, buckets):
    """One replica's stored array ``[G, C, 128]``."""
    return spec(packed_shape(_cfg(buckets))[1:])


_OPCODE = re.compile(r"^\s*(?:ROOT )?%\S+ = (\w+)\[([\d,]*)\]\S* ([\w-]+)\(")


def _ops_of_at_least(hlo: str, elems: int):
    """``(opcode, shape)`` of every instruction whose array result holds at
    least ``elems`` elements."""
    out = []
    for line in hlo.splitlines():
        m = _OPCODE.match(line)
        if m and m.group(2):
            n = 1
            for d in m.group(2).split(","):
                n *= int(d)
            if n >= elems:
                out.append((m.group(3), m.group(2)))
    return out


def test_h3_hash_compiles_for_v5e(spec):
    hlo = _compiled_hlo(
        functools.partial(h3_hash_pallas, block_n=1024, interpret=False),
        spec((WK, T * N)), spec((22, WK)))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("buckets,passes", [(1 << 18, 1), (1 << 22, 16)],
                         ids=["resident", "blocked"])
def test_xor_stream_compiles_for_v5e(spec, buckets, passes, monkeypatch):
    """The served stream as the server builds it (``engine.run_stream`` on
    pallas, table donated), compiled for the chip: the stored table reaches
    the kernel and comes back with no layout op between them."""
    monkeypatch.setattr(pk, "interpret_mode",
                        lambda interpret=None: bool(interpret))
    cfg = _cfg(buckets)
    table = XorHashTable(spec((cfg.index_bits, WK)),
                         spec(packed_shape(cfg)), cfg)
    assert kops.stream_bucket_tiles(table.packed) == passes
    stream = jax.jit(functools.partial(engine.run_stream, backend="pallas"),
                     donate_argnums=(0,))
    hlo = stream.lower(table, spec((T, N), jnp.int32), spec((T, N, WK)),
                       spec((T, N, WV))).compile().as_text()
    # the kernel's stable name, which the device trace's op events carry,
    # and the seam's scopes around it
    assert re.search(r"%xor_stream\.\d+ = .*custom_call_target="
                     r"\"tpu_custom_call\"", hlo)
    for scope in ("kv.h3", "kv.prepass", "kv.unpermute"):
        assert f"/{scope}/" in hlo, scope
    for scope in ("kv.table_pack", "kv.table_unpack"):
        assert scope not in hlo, scope
    # nothing but the kernel (whose tuple result holds the table) makes a
    # table-sized array: the smallest [k, B, S, W] store is the valid
    # plane, k * B * S words
    big = _ops_of_at_least(hlo, K * buckets * S)
    assert {op for op, _ in big} <= {"parameter", "bitcast",
                                     "get-tuple-element"}, big
    # the donated table parameter (flat argument 1) is the stored table
    # output (flat result 1): updated in place
    assert re.search(r"\{1\}: \(1, \{\}, (may|must)-alias\)", hlo), \
        hlo.splitlines()[0]


def test_bulk_place_compiles_for_v5e(spec):
    n, buckets = 4096, 1 << 14
    hlo = _compiled_hlo(
        functools.partial(bulk_place_pallas, buckets=buckets, slots=S,
                          interpret=False),
        spec((n,), jnp.int32), spec((n,), jnp.int32), spec((n, WK)),
        spec((n, WV)), _table(spec, buckets))
    assert "tpu_custom_call" in hlo


def test_xor_probe_compiles_for_v5e(spec):
    hlo = _compiled_hlo(
        functools.partial(xor_probe_pallas, k=K, slots=S, val_words=WV,
                          stagger=True, interpret=False,
                          vmem_limit=kops.VMEM_LIMIT_BYTES),
        spec((N,)), spec((N,), jnp.int32), spec((N, WK)),
        _table(spec, 1 << 14))
    assert "tpu_custom_call" in hlo


def test_xor_commit_compiles_for_v5e(spec):
    hlo = _compiled_hlo(
        functools.partial(xor_commit_pallas, buckets=1 << 14, slots=S,
                          interpret=False, vmem_limit=kops.VMEM_LIMIT_BYTES),
        _table(spec, 1 << 14), spec((N,), jnp.int32),
        spec((N,), jnp.int32), spec((N,), jnp.int32), spec((N, WK)),
        spec((N, WV)), spec((N,)))
    assert "tpu_custom_call" in hlo
