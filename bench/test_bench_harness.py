"""Tests of the table benchmark's harness, on the CPU.

They load every file ``BENCHMARK.json`` names, show that a new
configuration, traffic mix and metric load from new files alone, reduce a
trace recorded here, count the roofline's bytes by hand, rehearse every
cell at a tiny table with the kernels in interpret mode, and show that the
check reads ``correct`` false for the control and for each fault a cell can
have.  A cell on more than one chip, and a sharded configuration given
only as test data, run in a child process on as many virtual CPU devices.
No TPU is needed or described.

Run as a script, the file is that child: ``python bench/test_bench_harness.py
<job.json>`` runs one cell once per fault named in the job (``null``: the
served stream as it is) and prints each result line.
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


SEED = 2 ** 31 + 12345


def _run(cell, trace=False, **kw):
    """One rehearsal of a ``BENCHMARK.json`` cell at a tiny table; a cell on
    more than one chip runs in a child process (:func:`_run_devices`)."""
    import jax
    chips = kvbench.cell_entry(SPEC, cell)["chips"]
    if chips > 1:
        assert not kw, "the child takes a fault by name (_run_devices)"
        return _run_devices(SPEC, REPO, cell, chips, trace, [None])[0][0]
    return kvbench.run_cell(SPEC, cell, SEED, 1.0, trace,
                            t_start=time.perf_counter(),
                            devices=jax.devices()[:1], overrides=TINY, **kw)


def _run_devices(spec, root, cell, chips, trace, faults):
    """Runs ``cell`` in a child on ``chips`` virtual CPU devices, once per
    fault name in ``faults`` (``None``: the served stream); returns the
    result lines and the child's standard error."""
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        job = os.path.join(d, "job.json")
        with open(job, "w") as f:
            json.dump({"spec": spec, "root": root, "cell": cell,
                       "trace": trace, "faults": faults}, f)
        env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=(
            os.environ.get("XLA_FLAGS", "") +
            f" --xla_force_host_platform_device_count={chips}").strip())
        p = subprocess.run([sys.executable, os.path.abspath(__file__), job],
                           cwd=REPO, env=env, capture_output=True, text=True,
                           timeout=600)
    assert p.returncode == 0, p.stdout[-4000:] + p.stderr[-8000:]
    lines = [json.loads(x) for x in p.stdout.splitlines()
             if x.startswith("{")]
    assert len(lines) == len(faults), p.stdout[-4000:]
    return lines, p.stderr


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
                         devices=jax.devices()[:1], overrides=TINY)
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


def _update_on_search_lane(p, k, devices=1):
    """Moves the first update of each slab from its write lane to a lane
    whose PE has no write port, and its answer back.  PEs of lanes by the
    table's layout (``kvref.lane_pe``)."""
    import jax.numpy as jnp

    def wrap(stream):
        def run(t, o, k_, v):
            o_np = np.asarray(o)
            pe = kvref.lane_pe(o_np.shape[1], p, devices)
            at = np.argwhere((o_np == kvref.OP_UPDATE) & (pe < k))
            if not len(at):
                return stream(t, o, k_, v)
            step, a = at[0]
            b = int(np.flatnonzero(pe >= k)[0])

            def swap(x):
                x = np.array(x)
                x[step, [a, b]] = x[step, [b, a]]
                return jnp.asarray(x)
            t, res = stream(t, swap(o), swap(k_), swap(v))
            return t, dataclasses.replace(
                res, found=swap(res.found), ok=swap(res.ok),
                value=swap(res.value))
        return run
    return wrap


def _faults(p, k, devices=1):
    """The control and each fault a cell can have, by name, for a table of
    ``p`` PEs, ``k`` write ports over ``devices`` origin devices."""
    return {"control_stale_reads": StaleReads,
            "state_unchanged": _state_unchanged,
            "half_the_batch": _half_the_batch,
            "answer_altered": _answer_altered,
            "update_on_search_lane": _update_on_search_lane(p, k, devices)}


@pytest.mark.parametrize("fault", list(_faults(*SLAB_GEOMETRY)))
def test_control_and_faults_read_incorrect(fault):
    r = _run("ycsb_b.hbm", wrap_stream=_faults(*SLAB_GEOMETRY)[fault],
             donate=False)
    assert not r["correct"]
    assert any(c["value"] > 0 for c in r["checks"].values())
    if fault == "update_on_search_lane":
        assert r["checks"]["ok_mismatch"]["value"] > 0


# ---------------------------------------------------------------------------
# A sharded configuration as data: four virtual devices
# ---------------------------------------------------------------------------

MESH_TABLE = {"buckets": 1024, "shards": 4, "router": "bounded", "k": 2}
MESH_CELL = {"name": "ycsb_b.mesh_rehearsal", "config": "ycsb_mesh_rehearsal",
             "traffic": "ycsb_b", "chips": 4, "why": "x"}
MESH_READERS = {
    "plan_cache_hits":
        "def read(ctx):\n    return ctx['counters'].get('plan_cache.hits')\n",
    "window_compiles":
        "def read(ctx):\n    return ctx['window']['compiles']\n",
}


def _mesh_checkout(tmp_path):
    """A checkout holding only a sharded bounded-router configuration (the
    ``ycsb_hbm`` file with ``MESH_TABLE``'s fields), the YCSB-B mix, two
    readers of the program's counters, and the spec entries naming them."""
    bench = tmp_path / "bench"
    for d in ("configs", "traffic", "metrics"):
        (bench / d).mkdir(parents=True)
    cfg = kvbench.load_config(SPEC, "ycsb_hbm")
    cfg["table"].update(MESH_TABLE)
    t = kvbench.table_config(cfg)
    cfg["recordcount"] = int(cfg["load_factor"] * t.buckets * t.slots)
    cfg["deployment"] = {"chips": 4, "split": "4 bucket shards, one a chip"}
    name = MESH_CELL["config"]
    (bench / "configs" / f"{name}.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "ycsb_b.json").write_text(
        open(os.path.join(BENCH, "traffic", "ycsb_b.json")).read())
    for m, src in MESH_READERS.items():
        (bench / "metrics" / f"{m}.py").write_text(src)
    spec = json.loads(json.dumps(SPEC))
    spec["configs"].append({"name": name, "source": "x", "reduced": [],
                            "file": f"bench/configs/{name}.json", "why": "x"})
    spec["workloads"].append(MESH_CELL)
    spec["per_layer"] = [{"name": m, "unit": "x", "layer": "x",
                          "workloads": [MESH_CELL["name"]]}
                         for m in MESH_READERS]
    return spec, t


def test_sharded_configuration_runs_as_data(tmp_path):
    """``shards=4`` with the bounded router, given only as a configuration
    dict and spec entries, runs through ``kvbench.run_cell`` on four
    virtual devices: ``correct``, the server's plan cache used through
    ``Capture``, no program built in the window, a peak read from every
    device; an altered answer and an update on a search lane (a device
    ``>= k``) each read ``correct`` false."""
    spec, t = _mesh_checkout(tmp_path)
    assert t.mesh_devices == 4 and t.k < t.mesh_devices
    faults = [None, "answer_altered", "update_on_search_lane"]
    (ok, altered, search_lane), err = _run_devices(
        spec, str(tmp_path), MESH_CELL["name"], 4, True, faults)
    assert ok["correct"], ok["checks"]
    assert ok["failed"] == 0 and ok["attempted"] > 0
    assert ok["metrics"]["plan_cache_hits"]["value"] > 0
    assert ok["metrics"]["window_compiles"]["value"] == 0
    assert ok["device"]["count"] == 4
    peaks = [line for line in err.splitlines()
             if "memory peak by device" in line]
    assert len(peaks) == 3
    assert peaks[0].rsplit("[", 1)[1].count(",") == 3
    assert not altered["correct"]
    assert altered["checks"]["value_mismatch"]["value"] > 0
    assert not search_lane["correct"]
    assert search_lane["checks"]["ok_mismatch"]["value"] > 0


def test_reference_takes_the_lane_pe_rule_from_the_layout():
    """An update on lane 8 of a 16-lane step of a ``p=4, k=2`` table: PE
    ``8 % 4 = 0`` may write on one domain; on four devices the lane is
    origin device ``8 // 4 = 2 >= k``, which has no write port.  A run that
    wrote it reads correct under the first rule and not the second."""
    p, k, N = 4, 2, 16
    assert kvref.lane_pe(N, p).tolist() == [0, 1, 2, 3] * 4
    assert kvref.lane_pe(N, p, 4).tolist() == sum(([d] * 4 for d in
                                                   range(4)), [])
    q = np.array([[1, 0], [2, 0]], np.uint32)             # 2 bits, 4 buckets
    keys = np.array([[1, 7]], np.uint32)
    old, new = np.array([[5, 0]], np.uint32), np.array([[0, 9]], np.uint32)
    ops = np.zeros((1, 1, N), np.int32)
    ops[0, 0, 8] = kvref.OP_UPDATE
    skeys = np.zeros((1, 1, N, 2), np.uint32)
    skeys[0, 0, 8] = keys[0]
    svals = np.zeros((1, 1, N, 2), np.uint32)
    svals[0, 0, 8] = new[0]
    fk = np.zeros((4, 4, 2), np.uint32)
    fv = np.zeros((4, 4, 2), np.uint32)
    fvalid = np.zeros((4, 4), np.uint32)
    b = int(kvref.h3(keys, q)[0])
    fk[b, 0], fv[b, 0], fvalid[b, 0] = keys[0], new[0], 1
    run = dict(p=p, k=k, slots=4, loaded_keys=keys, loaded_vals=old,
               q_masks=q, kind=np.array([kvref.OP_UPDATE]),
               kid=np.array([0]), op_vals=new, answered=np.array([True]),
               found=np.array([True]), ok=np.array([True]), value=old,
               slab_ops=ops, slab_keys=skeys, slab_vals=svals,
               final_keys=fk, final_vals=fv, final_valid=fvalid)
    one, _ = kvref.compare(**run)
    assert all(v == 0 for v in one.values()), one
    mesh, _ = kvref.compare(**run, devices=4)
    assert mesh["state_mismatch"] == 1, mesh


def test_memory_peak_is_the_fullest_device():
    class Dev:
        def __init__(self, peak):
            self.peak = peak

        def memory_stats(self):
            return None if self.peak is None else {
                "peak_bytes_in_use": self.peak}
    assert kvbench.memory_peaks([Dev(5), Dev(9), Dev(7), Dev(None)]) == [
        5, 9, 7, 0]
    assert kvbench.memory_peaks([Dev(3)]) == [3]


def test_command_refuses_chips_unlike_the_table(tmp_path):
    """``bench/run.py`` refuses a cell whose ``chips`` differ from the
    devices its configuration lays the table over, before it looks for a
    chip."""
    spec = json.loads(json.dumps(SPEC))
    spec["workloads"].append({"name": "ycsb_b.resident4",
                              "config": "ycsb_resident", "traffic": "ycsb_b",
                              "chips": 4, "why": "x"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    os.symlink(BENCH, tmp_path / "bench")
    os.symlink(os.path.join(REPO, "src"), tmp_path / "src")
    with pytest.raises(ValueError, match="4 chips.*mesh_devices = 1"):
        kvbench.cell_config(spec, "ycsb_b.resident4")
    p = subprocess.run([sys.executable, str(tmp_path / "bench" / "run.py"),
                        "--workload", "ycsb_b.resident4", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       cwd=tmp_path, env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "4 chips" in p.stderr and "mesh_devices = 1" in p.stderr


# ---------------------------------------------------------------------------
# The child of _run_devices
# ---------------------------------------------------------------------------

def _child(job_path: str) -> None:
    import jax
    with open(job_path) as f:
        job = json.load(f)
    spec, cell = job["spec"], job["cell"]
    cfg = kvbench.table_config(kvbench.cell_config(spec, cell, job["root"]),
                               TINY)
    faults = _faults(cfg.p, cfg.k, cfg.mesh_devices)
    for name in job["faults"]:
        r = kvbench.run_cell(spec, cell, SEED, 1.0, job["trace"],
                             t_start=time.perf_counter(),
                             devices=jax.devices(), overrides=TINY,
                             wrap_stream=faults.get(name),
                             donate=name is None, root=job["root"])
        print(json.dumps(r), flush=True)


if __name__ == "__main__":
    _child(sys.argv[1])
