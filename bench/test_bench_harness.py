"""Tests of the table benchmark's harness, on the CPU.

They load every file ``BENCHMARK.json`` names, show that a new
configuration, traffic mix and metric load from new files alone, reduce a
trace recorded here, count the roofline's bytes by hand, rehearse every
cell at a tiny table with the kernels in interpret mode, and show that the
check reads ``correct`` false for the control and for each fault a cell can
have.  No TPU is needed or described.
"""
from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(BENCH)
for _p in (os.path.join(REPO, "src"), BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import kvbench  # noqa: E402
import kvref  # noqa: E402
import trace_reduce  # noqa: E402
from calibrate import StaleReads  # noqa: E402

SPEC = kvbench.load_spec()
CELLS = [w["name"] for w in SPEC["workloads"]]
TINY = {"buckets": 1024}
_RES = kvbench.table_config(kvbench.load_config(SPEC, "ycsb_resident"))
SLAB_GEOMETRY = (_RES.p, _RES.k)


def _run(cell, trace=False, **kw):
    import jax
    return kvbench.run_cell(SPEC, cell, 2 ** 31 + 12345, 1.0, trace,
                            t_start=time.perf_counter(),
                            device=jax.devices()[0], n_devices=1,
                            overrides=TINY, **kw)


# ---------------------------------------------------------------------------
# Files, found by name
# ---------------------------------------------------------------------------

def test_every_named_file_loads():
    names = {c["name"] for c in SPEC["configs"]}
    for w in SPEC["workloads"]:
        assert w["config"] in names
        kvbench.load_traffic(w["traffic"])
        kvbench.table_config(kvbench.load_config(SPEC, w["config"]))
    for m in SPEC["per_layer"]:
        assert callable(kvbench.load_metric(m["name"]).read)
        assert m["moves"] in {e["name"] for e in SPEC["end_to_end"]}
    for c in SPEC["configs"]:
        cfg = kvbench.load_config(SPEC, c["name"])
        t = kvbench.table_config(cfg)
        assert cfg["recordcount"] == int(cfg["load_factor"] * t.buckets
                                         * t.slots)
    assert kvbench.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_unknown_device_kind_is_an_error():
    with pytest.raises(ValueError, match="no peaks"):
        kvbench.peaks_for("TPU v99")


def test_new_cell_loads_from_new_files_alone(tmp_path):
    bench = tmp_path / "bench"
    for d in ("configs", "traffic", "metrics"):
        (bench / d).mkdir(parents=True)
    spec = json.loads(json.dumps(SPEC))
    cfg = kvbench.load_config(SPEC, "ycsb_resident")
    cfg["table"]["k"] = 8
    (bench / "configs" / "ycsb_k8.json").write_text(json.dumps(cfg))
    spec["configs"].append({"name": "ycsb_k8", "source": "x", "reduced": [],
                            "file": "bench/configs/ycsb_k8.json", "why": "x"})
    mix = json.load(open(os.path.join(BENCH, "traffic", "ycsb_a.json")))
    mix["read"], mix["update"] = 0.9, 0.1
    (bench / "traffic" / "ycsb_90.json").write_text(json.dumps(mix))
    (bench / "metrics" / "lanes.py").write_text(
        "def read(ctx):\n    return ctx['counters']['live_lanes']\n")
    spec["workloads"].append({"name": "ycsb_90.k8", "config": "ycsb_k8",
                              "traffic": "ycsb_90", "chips": 1, "why": "x"})
    spec["per_layer"].append({"name": "lanes", "workloads": ["ycsb_90.k8"]})
    assert kvbench.table_config(
        kvbench.load_config(spec, "ycsb_k8", root=str(tmp_path))).k == 8
    assert kvbench.load_traffic("ycsb_90", bench=str(bench)).read == 0.9
    reader = kvbench.load_metric("lanes", bench=str(bench))
    assert reader.read({"counters": {"live_lanes": 7}}) == 7
    assert [m["name"] for m in kvbench.cell_metrics(
        spec, "ycsb_90.k8", "per_layer")] == ["lanes"]


def test_roofline_bytes_match_a_hand_count():
    cfg = kvbench.table_config(kvbench.load_config(SPEC, "ycsb_resident"))
    geo = {"p": cfg.p, "k": cfg.k, "slots": cfg.slots,
           "key_words": cfg.key_words, "val_words": cfg.val_words}
    roof = kvbench.load_metric("xor_stream_roofline")
    # column: 4 planes x 4 slots x (2 key + 2 value + 1 valid) words x 4 B;
    # query: op + 2 key + 2 value words; result: flags + 2 value words
    assert roof.work_bytes(geo, 1, 0) == 320 + 20 + 12
    assert roof.work_bytes(geo, 4096, 100) == 4096 * 352 + 100 * 320
    ctx = {"trace": {"op_seconds": {"xor_stream_kernel": 1e-3}},
           "work": {"live_lanes": 4096, "accepted_mutations": 0},
           "geometry": geo, "peak": {"hbm_bytes_per_s": 819e9}}
    assert roof.read(ctx) == pytest.approx(100 * 4096 * 352 / 819e9 / 1e-3)


# ---------------------------------------------------------------------------
# Trace reduction
# ---------------------------------------------------------------------------

def test_merge_and_clip():
    assert trace_reduce.merge([(5, 7), (0, 2), (1, 3), (6, 9)]) == [
        (0, 3), (5, 9)]
    assert trace_reduce.clip([("a", 0, 10), ("b", 12, 14)], 5, 13) == [
        ("a", 5, 10), ("b", 12, 13)]


def test_trace_reduce_on_a_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((512, 512))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.wait"):
                time.sleep(0.01)
    jax.profiler.stop_trace()
    s = trace_reduce.summarize(trace_reduce.load(str(tmp_path)), "cpu")
    assert 0.03 <= s["window_s"] < 5
    assert 0 < s["busy_s"] < s["window_s"]
    assert any("dot" in name for name, _ in s["top_ops"])
    assert sum(s["op_seconds"].values()) >= s["busy_s"] * 0.99
    gaps = dict(s["idle_gaps"])
    assert "bench.wait" in gaps and gaps["bench.wait"] >= 0.009
    ctx = {"trace": s}
    idle = kvbench.load_metric("idle_share").read(ctx)
    assert idle == pytest.approx(100 * (1 - s["busy_s"] / s["window_s"]))


# ---------------------------------------------------------------------------
# The reference
# ---------------------------------------------------------------------------

def test_reference_h3_and_bulk_load():
    keys = np.array([[1, 0], [2, 0], [3, 0], [0, 1]], np.uint32)
    q = np.array([[1, 0], [2, 1]], np.uint32)      # bit0: key&1, bit1: key&2^w1
    assert kvref.h3(keys, q).tolist() == [1, 2, 3, 2]
    # three keys in one bucket, two slots: the third in load order spills
    same = np.array([[0, 0], [4, 0], [8, 0], [1, 0]], np.uint32)
    assert kvref.bulk_load(same, q, 2).tolist() == [True, True, False, True]


def test_reference_h3_is_the_parity_of_each_mask():
    rng = np.random.default_rng(3)
    keys = rng.integers(0, 2 ** 32, size=(500, 2), dtype=np.uint32)
    q = rng.integers(0, 2 ** 32, size=(18, 2), dtype=np.uint32)
    want = np.zeros(len(keys), np.int64)
    for j, (m0, m1) in enumerate(q):
        ones = (np.bitwise_count(keys[:, 0] & m0)
                + np.bitwise_count(keys[:, 1] & m1))
        want |= (ones.astype(np.int64) & 1) << j
    assert (kvref.h3(keys, q) == want).all()


def test_every_loaded_record_has_room():
    """The load leaves no key out, so the traffic's every read finds its
    key and every update is acknowledged."""
    import kvtraffic
    n = int(0.6 * 1024 * 4)
    for seed in (1, 2 ** 31 + 7):
        loaded = kvtraffic.make_loaded(n, 10, 2, 2, 4, seed)
        assert len(np.unique(kvref.pack64(loaded.keys))) == n
        assert kvref.bulk_load(loaded.keys, loaded.q_masks, 4).all()


# ---------------------------------------------------------------------------
# Rehearsals: every cell at a tiny table, the command off the chip
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearsal(cell):
    r = _run(cell)
    assert r["correct"], r["checks"]
    want = {m["name"] for m in kvbench.cell_metrics(SPEC, cell,
                                                    "end_to_end")}
    assert set(r["metrics"]) == want
    assert all(v["value"] > 0 for v in r["metrics"].values())
    assert r["attempted"] > 0 and list(r)[-1] == "checks"
    assert r["failed"] == 0


OPEN_CELL = {"name": "ycsb_b.resident.open", "config": "ycsb_resident",
             "traffic": "ycsb_b.open", "chips": 1, "why": "x"}


@pytest.mark.parametrize("trace", [False, True], ids=["e2e", "traced"])
def test_open_loop_rehearsal(trace):
    """The open loop and its readers, kept for the open cell (PERF.md)."""
    import jax
    spec = json.loads(json.dumps(SPEC))
    spec["workloads"].append(OPEN_CELL)
    spec["end_to_end"] += [
        {"name": "p50_ms", "unit": "ms", "workloads": [OPEN_CELL["name"]]},
        {"name": "p99_ms", "unit": "ms", "workloads": [OPEN_CELL["name"]]}]
    spec["per_layer"] = [
        {"name": n, "unit": "x", "workloads": [OPEN_CELL["name"]]}
        for n in ("pad_fraction.open", "step_max_ms.open")]
    r = kvbench.run_cell(spec, OPEN_CELL["name"], 2 ** 31 + 99, 1.0, trace,
                         t_start=time.perf_counter(),
                         device=jax.devices()[0], n_devices=1,
                         overrides=TINY)
    assert r["correct"], r["checks"]
    want = ({"pad_fraction.open", "step_max_ms.open"} if trace
            else {"p50_ms", "p99_ms", "setup_s"})
    assert set(r["metrics"]) == want
    assert all(v["value"] > 0 for v in r["metrics"].values())


def test_traced_rehearsal_reports_counters_and_device_time():
    r = _run("ycsb_a.resident", trace=True)
    assert r["correct"], r["checks"]
    assert 0.3 < r["metrics"]["pad_fraction.closed"]["value"] < 0.7
    assert 0 < r["device"]["busy_s"] <= r["device"]["window_s"]
    assert r["breakdown"]["device_ops"] and r["breakdown"]["idle_gaps"]


def test_command_refuses_off_the_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(BENCH, "run.py"),
                        "--workload", CELLS[-1], "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       cwd=REPO, env=env, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


# ---------------------------------------------------------------------------
# The control and the faults read incorrect
# ---------------------------------------------------------------------------

def _state_unchanged(stream):
    return lambda t, o, k, v: (t, stream(t, o, k, v)[1])


def _half_the_batch(stream):
    def run(t, o, k, v):
        return stream(t, o.at[o.shape[0] // 2:].set(0), k, v)
    return run


def _answer_altered(stream):
    import jax.numpy as jnp

    def run(t, o, k, v):
        t, res = stream(t, o, k, v)
        flat = res.value.reshape(-1, res.value.shape[-1])
        i = jnp.argmax(o.reshape(-1) != 0)
        flat = flat.at[i, 0].set(flat[i, 0] ^ 1)
        return t, dataclasses.replace(res, value=flat.reshape(res.value.shape))
    return run


def _update_on_search_lane(stream):
    """Moves the first update of each slab from its write lane to a lane
    whose PE has no write port, and its answer back."""
    import jax.numpy as jnp

    def run(t, o, k, v):
        o_np = np.asarray(o)
        p, kk = SLAB_GEOMETRY
        pe = np.arange(o_np.shape[1]) % p
        at = np.argwhere((o_np == kvref.OP_UPDATE) & (pe < kk))
        if not len(at):
            return stream(t, o, k, v)
        step, a = at[0]
        b = int(np.flatnonzero(pe >= kk)[0])

        def swap(x):
            x = np.array(x)
            x[step, [a, b]] = x[step, [b, a]]
            return jnp.asarray(x)
        t, res = stream(t, swap(o), swap(k), swap(v))
        return t, dataclasses.replace(res, found=swap(res.found),
                                      ok=swap(res.ok), value=swap(res.value))
    return run


@pytest.mark.parametrize("wrap", [StaleReads, _state_unchanged,
                                  _half_the_batch, _answer_altered,
                                  _update_on_search_lane],
                         ids=["control_stale_reads", "state_unchanged",
                              "half_the_batch", "answer_altered",
                              "update_on_search_lane"])
def test_control_and_faults_read_incorrect(wrap):
    r = _run("ycsb_b.hbm", wrap_stream=wrap, donate=False)
    assert not r["correct"]
    assert any(c["value"] > 0 for c in r["checks"].values())
    if wrap is _update_on_search_lane:
        assert r["checks"]["ok_mismatch"]["value"] > 0
