"""Tests of ``bench/spans.py``, on the CPU: the reduction of the program's
spans and scopes on hand-built traces and a hand-written program text, and
a traced rehearsal of a cell at a tiny table."""
from __future__ import annotations

import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(BENCH)
for _p in (os.path.join(REPO, "src"), BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import kvbench  # noqa: E402
import spans  # noqa: E402
import trace_reduce  # noqa: E402

SPEC = kvbench.load_spec()
CPU_OPS = "tf_XLAPjRtCpuClient/1"


def _trace(host, ops):
    """A CPU trace: ``host`` spans on the ``python`` line and device
    ``ops`` on the client's line, each ``(name, start_us, end_us)``."""
    names = sorted({n for n, _, _ in host + ops})
    ids = {n: i + 1 for i, n in enumerate(names)}

    def line(i, name, evs):
        body = "".join(
            f"events {{ metadata_id: {ids[n]} offset_ps: {s * 10**6} "
            f"duration_ps: {(e - s) * 10**6} }}\n" for n, s, e in evs)
        return f'lines {{ id: {i} name: "{name}" timestamp_ns: 0\n{body}}}\n'

    meta = "".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                   f'name: "{n}" }} }}\n' for n, i in ids.items())
    text = ('planes { id: 1 name: "/host:CPU"\n' + line(1, "python", host)
            + line(2, CPU_OPS, ops) + meta + "}\n")
    from jax.profiler import ProfileData
    return ProfileData.from_text_proto(text)


# A window of 100 us: two requests submitted (the second's gap of 10 us in
# kv.submit), one step that packs, dispatches and retires (waiting 20 us).
BENCH_SPANS = [("bench.window", 0, 100), ("bench.submit", 0, 30),
               ("bench.step", 30, 90)]
KV_SPANS = [("kv.submit", 2, 10), ("kv.submit", 12, 28),
            ("kv.step", 31, 89), ("kv.pack", 32, 40),
            ("kv.dispatch", 40, 50), ("kv.retire", 52, 88),
            ("kv.retire.wait", 55, 75)]
OPS = [("fusion.1", 0, 15), ("xor_stream.1", 25, 45), ("copy.2", 45, 60),
       ("fusion.3", 70, 80)]


def test_self_time_nesting_and_idle_on_a_hand_built_trace():
    scope_of = {"fusion.1": "kv.table_pack", "copy.2": "kv.table_unpack",
                "xor_stream.1": "xor_stream"}
    red = spans.reduce_trace(_trace(BENCH_SPANS + KV_SPANS, OPS), "cpu",
                             scope_of)
    us = {n: v * 1e6 for n, v in red["span_self_s"].items()}
    assert us == pytest.approx({"kv.submit": 24, "kv.step": 58 - 8 - 10 - 36,
                                "kv.pack": 8, "kv.dispatch": 10,
                                "kv.retire": 16, "kv.retire.wait": 20})
    assert red["span_count"] == {"kv.submit": 2, "kv.step": 1, "kv.pack": 1,
                                 "kv.dispatch": 1, "kv.retire": 1,
                                 "kv.retire.wait": 1}
    idle = {n: v * 1e6 for n, v in red["idle_by_span"].items()}
    # the device idles over [15, 25) (in the second kv.submit, inside
    # bench.submit), [60, 70) and [80, 100)
    assert idle == pytest.approx({"kv.submit": 10, "kv.retire.wait": 10,
                                  "kv.retire": 8, "kv.step": 1,
                                  "bench.step": 1, "host:other": 10})
    assert sum(red["idle_by_span"].values()) == pytest.approx(
        red["window_s"] - red["busy_s"])
    step = red["longest_step"]
    assert [c[0] for c in step["children"]] == ["kv.pack", "kv.dispatch",
                                                "kv.retire"]
    assert [c[1:] for c in step["children"]] == [
        pytest.approx([0.008, 0.008]), pytest.approx([0.010, 0.010]),
        pytest.approx([0.036, 0.016])]
    assert step["self_ms"] == pytest.approx(0.004)
    assert {n: v * 1e6 for n, v in red["scope_seconds"].items()} == (
        pytest.approx({"kv.table_pack": 15, "kv.table_unpack": 15,
                       "xor_stream": 20, "unscoped": 10}))
    assert red["kernel_s"] == pytest.approx(20e-6)
    n = spans.layer_numbers(red, slabs=1)
    assert n["submit_us_per_op"] == pytest.approx(12)
    assert n["pack_ms_per_slab"] == pytest.approx(0.008)
    assert n["dispatch_ms_per_slab"] == pytest.approx(0.010)
    assert n["retire_ms_per_slab"] == pytest.approx(0.016)
    assert n["table_repack_ms_per_slab"] == pytest.approx(0.030)
    assert n["idle_in_server_share"] == pytest.approx(100 * 29 / 40)


def test_program_spans_leave_the_harness_summary_as_it_was():
    """The accepted reduction reads the same with the program's spans in
    the trace as without them: its idle gaps keep the harness's labels."""
    without = trace_reduce.summarize(_trace(BENCH_SPANS, OPS), "cpu")
    with_kv = trace_reduce.summarize(_trace(BENCH_SPANS + KV_SPANS, OPS),
                                     "cpu")
    assert set(without) == {"window_s", "busy_s", "op_seconds", "top_ops",
                            "idle_gaps"}
    assert with_kv == without
    assert {g[0] for g in without["idle_gaps"]} == {"bench.submit",
                                                    "bench.step"}


HLO = """\
HloModule jit_run_stream

%fused_computation (param_0: u32[8]) -> u32[8] {
  %param_0 = u32[8]{0} parameter(0)
  ROOT %add.9 = u32[8]{0} add(%param_0, %param_0), metadata={op_name="jit(f)/kv.table_pack/add"}
}

ENTRY %main.1 (table.1: u32[8], keys.1: u32[8]) -> u32[8] {
  %table.1 = u32[8]{0} parameter(0), metadata={op_name="table"}
  %keys.1 = u32[8]{0} parameter(1), metadata={op_name="keys"}
  %copy-start.1 = (u32[8]{0}, u32[8]{0}, u32[]) copy-start(%table.1)
  %copy-done.1 = u32[8]{0} copy-done(%copy-start.1)
  %add_fusion = u32[8]{0} fusion(%copy-done.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/kv.table_pack/add"}
  %hash.2 = u32[8]{0} xor(%keys.1, %keys.1), metadata={op_name="jit(f)/kv.h3/jit(h3_hash)/xor"}
  %xor_stream.1 = u32[8]{0} custom-call(%add_fusion, %hash.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/jit(xor_stream_pallas)/xor_stream/pallas_call"}
  %copy.3 = u32[8]{0} copy(%xor_stream.1), metadata={op_name="jit(f)/jit(xor_stream_pallas)/xor_stream/pallas_call"}
  %reshape.4 = u32[8]{0} reshape(%copy.3), metadata={op_name="jit(f)/kv.table_unpack/reshape"}
  %copy.5 = u32[8]{0} copy(%reshape.4)
  ROOT %sub.6 = u32[8]{0} subtract(%copy.5, %copy.5)
}
"""


def test_scope_map_reads_op_names_and_follows_layout_copies():
    m = spans.scope_map(HLO)
    assert m["add_fusion"] == m["add.9"] == "kv.table_pack"
    assert m["hash.2"] == "kv.h3"
    assert m["xor_stream.1"] == m["copy.3"] == "xor_stream"
    assert m["reshape.4"] == "kv.table_unpack"
    # copies XLA added: the table's, before its user; the result's, after
    # its operand
    assert m["copy-done.1"] == m["copy-start.1"] == "kv.table_pack"
    assert m["copy.5"] == m["sub.6"] == "kv.table_unpack"
    seconds = spans.scope_seconds({"add_fusion": 2.0, "copy-done.1": 1.0,
                                   "xor_stream.1": 4.0, "other.7": 0.5}, m)
    assert seconds == {"kv.table_pack": 3.0, "xor_stream": 4.0,
                       "unscoped": 0.5}


def test_traced_rehearsal_reads_the_host_layers():
    """The command's run at a tiny table.  The CPU trace records few of
    XLA:CPU's operations, so the device layers (``table_repack``, the
    kernel) are read on the chip only."""
    import jax
    r = spans.traced_run(SPEC, "ycsb_a.resident", 2 ** 31 + 12345, 1.0, True,
                         jax.devices()[0].platform, 0.0,
                         overrides={"buckets": 1024})
    json.dumps(r)
    assert set(r["layer"]) >= {
        "submit_us_per_op", "pack_ms_per_slab", "dispatch_ms_per_slab",
        "retire_ms_per_slab", "idle_in_server_share"}
    assert all(v >= 0 for v in r["layer"].values())
    assert r["span_count"]["kv.submit"] == r["requests"]
    assert r["span_count"]["kv.dispatch"] == r["slabs"] > 0
    assert sum(r["idle_by_span"].values()) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-6)
    assert r["longest_step"]["children"]
    assert set(r["scope_seconds"]) - {"unscoped"} <= {
        "kv.h3", "kv.prepass", "kv.table_pack", "kv.table_unpack",
        "kv.unpermute", "xor_stream"}
