"""The table benchmark's harness: one cell, one run.

A cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``bench/configs/<name>.json``) and a traffic mix
(``bench/traffic/<name>.json``); its metrics are the ``BENCHMARK.json``
entries that list it, and each per-layer metric is read by
``bench/metrics/<name>.py``.  Nothing here names a cell, a configuration
or a metric, so a later change adds any of them as files and entries.

A run:

1. set-up (``setup_s``): the compile cache, the records, the H3 matrix and
   the requests made from the seed; ``engine.bulk_build``; the served
   stream (``engine.run_stream`` on the fused Pallas kernel, jitted for the
   one ``[slab_steps, p]`` slab shape, table donated) compiled and warmed;
   the ``TableServer``;
2. the window: ``--seconds`` of a closed loop (``clients`` each with one
   request outstanding) or an open loop (requests at seeded arrival
   times), through ``TableServer.submit`` / ``TableServer.step``, the
   device profiled when ``trace`` is set.  Every request is one operation
   from the pool made in set-up; nothing is generated in the window, and
   Python's collector is held off it (the harness keeps every request for
   the check, which a server would have freed);
3. the check: once the window has closed and the server has drained, the
   peak device memory is read, the table's final plaintext is fetched and
   the program's state freed; then ``kvref.compare`` holds every answer
   and the final table to the plain reference.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Callable, Dict, List, Optional

import numpy as np

import kvref
import kvtraffic
import trace_reduce

BENCH = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(BENCH)
CONFIG_KEYS = {"source", "table", "recordcount", "load_factor",
               "slab_steps", "guarantees", "record", "assumed", "reduced",
               "why"}


# ---------------------------------------------------------------------------
# The benchmark's files, found by name
# ---------------------------------------------------------------------------

def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_spec(root: str = REPO) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def load_config(spec: dict, name: str, root: str = REPO) -> dict:
    entry = next(c for c in spec["configs"] if c["name"] == name)
    cfg = load_json(os.path.join(root, entry["file"]))
    extra = set(cfg) - CONFIG_KEYS
    if extra:
        raise ValueError(f"{entry['file']}: unknown keys {sorted(extra)}")
    return cfg


def load_traffic(name: str, bench: str = BENCH) -> kvtraffic.Traffic:
    return kvtraffic.Traffic.from_json(
        load_json(os.path.join(bench, "traffic", f"{name}.json")))


def load_metric(name: str, bench: str = BENCH):
    """The reader module ``bench/metrics/<name>.py`` (its ``read(ctx)``)."""
    path = os.path.join(bench, "metrics", f"{name}.py")
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def peaks_for(device_kind: str, bench: str = BENCH) -> dict:
    table = load_json(os.path.join(bench, "peaks.json"))["devices"]
    if device_kind not in table:
        raise ValueError(f"no peaks for device kind {device_kind!r} in "
                         f"bench/peaks.json (known: {sorted(table)})")
    return table[device_kind]


def cell_metrics(spec: dict, cell: str, group: str) -> List[dict]:
    """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
    return [m for m in spec[group]
            if "workloads" not in m or cell in m["workloads"]]


def cell_entry(spec: dict, cell: str) -> dict:
    for w in spec["workloads"]:
        if w["name"] == cell:
            return w
    raise ValueError(f"no workload {cell!r} in BENCHMARK.json")


# ---------------------------------------------------------------------------
# Set-up
# ---------------------------------------------------------------------------

class Capture:
    """The stream the server is given: calls the served stream and keeps
    each dispatched slab's inputs and results for the check."""

    def __init__(self, stream: Callable):
        self.stream = stream
        self.slabs: list = []

    def __call__(self, table, ops, keys, vals):
        table, res = self.stream(table, ops, keys, vals)
        self.slabs.append((ops, keys, vals, res))
        return table, res


@dataclasses.dataclass
class Cell:
    cfg: object                     # HashTableConfig
    server: object                  # TableServer
    capture: Capture
    loaded: kvtraffic.Loaded
    requests: kvtraffic.RequestStream
    traffic: kvtraffic.Traffic
    spilled: int


def table_config(config: dict, overrides: Optional[dict] = None):
    from repro.core import HashTableConfig
    table = dict(config["table"])
    table.update(overrides or {})
    return HashTableConfig(**table)


def build(config: dict, traffic: kvtraffic.Traffic, seed: int, *,
          overrides: Optional[dict] = None,
          wrap_stream: Optional[Callable] = None, donate: bool = True
          ) -> Cell:
    """Everything before the window.  ``overrides`` replaces table fields
    (the tests' tiny tables); ``wrap_stream`` puts a changed stream in the
    served one's place (the control and the fault tests)."""
    import jax
    import jax.numpy as jnp
    from repro.core import engine, init_table
    from repro.serving.engine import ServeConfig, TableServer

    cfg = table_config(config, overrides)
    records = int(config["load_factor"] * cfg.buckets * cfg.slots)
    if not overrides and records != config["recordcount"]:
        raise ValueError(f"recordcount {config['recordcount']} is not "
                         f"load_factor x buckets x slots = {records}")
    loaded = kvtraffic.make_loaded(records, cfg.index_bits, cfg.key_words,
                                   cfg.val_words, cfg.slots, seed)
    table = init_table(cfg, jax.random.key(0))
    table = dataclasses.replace(table, q_masks=jnp.asarray(loaded.q_masks))
    table, report = engine.bulk_build(table, jnp.asarray(loaded.keys),
                                      jnp.asarray(loaded.vals))
    spilled = int(np.asarray(report.spilled).sum())

    T, N = config["slab_steps"], cfg.queries_per_step
    stream = jax.jit(functools.partial(engine.run_stream, backend="pallas"),
                     donate_argnums=(0,) if donate else ())
    nop = (jnp.zeros((T, N), jnp.int32),
           jnp.zeros((T, N, cfg.key_words), jnp.uint32),
           jnp.zeros((T, N, cfg.val_words), jnp.uint32))
    table, res = stream(table, *nop)              # compile + warm
    jax.block_until_ready((table, res))
    if wrap_stream is not None:
        stream = wrap_stream(stream)
    capture = Capture(stream)
    server = TableServer(cfg, table, capture,
                         ServeConfig(slab_steps=T, geometry_replan=False))

    requests = kvtraffic.RequestStream(traffic, loaded, seed)
    gc.collect()
    gc.freeze()
    return Cell(cfg, server, capture, loaded, requests, traffic, spilled)


# ---------------------------------------------------------------------------
# The window
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Window:
    t0: float
    deadline: float
    submitted: list                 # SlabRequest of request i, in order
    counters: Dict[str, int]        # server counters' change in the window
    slab_range: tuple               # captured slabs dispatched in the window
    lateness_s: np.ndarray          # open loop: submit time - arrival time
    step_max_s: float               # longest TableServer.step() in the window
    compiles: int                   # programs built inside the window
    trace: Optional[dict] = None    # trace_reduce.summarize of the window


def _counters(server) -> Dict[str, int]:
    return {"slabs": server.slabs, "live_lanes": server.live_lanes,
            "pad_lanes": server.pad_lanes}


class _CompileCounter:
    """Counts the programs JAX builds or fetches from its cache while the
    ``with`` block runs."""
    BUILD = "/jax/core/compile/backend_compile_duration"
    FETCH = "/jax/compilation_cache/cache_hits"

    def __enter__(self):
        import jax.monitoring as mon
        self.count = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)
        return self

    def __exit__(self, *exc):
        import jax.monitoring as mon
        mon.unregister_event_duration_listener(self._duration)
        mon.unregister_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        self.count += event == self.BUILD

    def _event(self, event, **kw):
        self.count += event == self.FETCH


def _profiler_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0        # host spans only, no per-call trace
    opts.host_tracer_level = 2
    return opts


class _Steps:
    """``server.step()`` under the ``bench.step`` span, keeping the longest
    call."""

    def __init__(self, server):
        self.server, self.span, self.max_s = server, _span(), 0.0

    def __call__(self):
        t = time.perf_counter()
        with self.span("bench.step"):
            rep = self.server.step()
        self.max_s = max(self.max_s, time.perf_counter() - t)
        return rep


def _closed_loop(cell: Cell, t0: float, seconds: float, submit, steps,
                 lateness) -> int:
    """``clients`` requests outstanding; each answer sends the client's
    next request until the deadline.  Returns the requests due and not
    sent: none."""
    span = _span()
    deadline = t0 + seconds
    for _ in range(cell.traffic.clients):
        submit()
    while True:
        rep = steps()
        if time.perf_counter() >= deadline:
            return 0
        with span("bench.submit"):
            for _ in rep.finished:
                submit()


def _open_loop(cell: Cell, t0: float, seconds: float, submit, steps,
               lateness) -> int:
    """Each request sent at its arrival time (or as soon after as the loop
    gets to it); returns how many arrivals of the window it had not sent
    when the deadline came."""
    span = _span()
    rs = cell.requests
    arr, P = rs.arrival, cell.traffic.pool_ops
    cycle = float(arr[-1])
    i, busy = 0, False

    def due(i):
        return (i // P) * cycle + float(arr[i % P])

    nxt = due(0)
    while True:
        now = time.perf_counter() - t0
        if now >= seconds:
            n = 0
            while due(i + n) < seconds:
                n += 1
            return n
        if nxt <= now:
            with span("bench.submit"):
                while nxt <= now:
                    submit()
                    lateness.append(now - nxt)
                    i += 1
                    nxt = due(i)
            busy = True
        if busy:
            busy = not steps().quiescent
        else:
            with span("bench.wait"):
                wait = nxt - (time.perf_counter() - t0)
                if wait > 0:
                    time.sleep(wait)


def _span():
    import jax
    return jax.profiler.TraceAnnotation


def run_window(cell: Cell, seconds: float, trace_dir: Optional[str] = None,
               platform: str = "tpu") -> Window:
    """Serve ``seconds`` of the cell's traffic, then drain the server."""
    import jax
    srv, rs = cell.server, cell.requests
    submitted: list = []
    lateness: List[float] = []

    def submit() -> None:
        submitted.append(srv.submit(*rs.request(len(submitted))))

    drive = _closed_loop if cell.traffic.loop == "closed" else _open_loop
    steps = _Steps(srv)
    if trace_dir is not None:
        jax.profiler.start_trace(trace_dir,
                                 profiler_options=_profiler_options())
    gc.disable()
    try:
        with _CompileCounter() as compiles:
            c0, s0 = _counters(srv), len(cell.capture.slabs)
            t0 = time.perf_counter()
            with _span()("bench.window"):
                late = drive(cell, t0, seconds, submit, steps, lateness)
            c1, s1 = _counters(srv), len(cell.capture.slabs)
        for _ in range(late):            # arrivals the loop had not reached
            lateness.append(time.perf_counter() - t0
                            - float(rs.arrival_s(len(submitted))))
            submit()
        if trace_dir is not None:
            jax.profiler.stop_trace()
        while not srv.step().quiescent:  # drain: late answers still count
            pass
    finally:
        gc.enable()
    summary = (trace_reduce.summarize(trace_reduce.load(trace_dir), platform)
               if trace_dir is not None else None)
    return Window(t0, t0 + seconds, submitted,
                  {k: c1[k] - c0[k] for k in c0}, (s0, s1),
                  np.asarray(lateness), steps.max_s, compiles.count, summary)


# ---------------------------------------------------------------------------
# The check, after the window
# ---------------------------------------------------------------------------

def fetch_final(cell: Cell):
    """The table's plaintext records as numpy, then the program's state
    dropped."""
    keys, vals, valid = cell.server.table.plaintext()
    out = (np.asarray(keys), np.asarray(vals), np.asarray(valid))
    cell.server.table = None
    return out


def fetch_slabs(cell: Cell):
    slabs = cell.capture.slabs
    ops = np.stack([np.asarray(s[0]) for s in slabs])
    keys = np.stack([np.asarray(s[1]) for s in slabs])
    vals = np.stack([np.asarray(s[2]) for s in slabs])
    ok = np.stack([np.asarray(s[3].ok) for s in slabs])
    cell.capture.slabs = []
    return ops, keys, vals, ok


def answers(win: Window):
    """``(answered [n], found [n], ok [n], value [n, Wv])`` of the ``n``
    one-operation requests sent."""
    subs = win.submitted
    return (np.fromiter((s.done for s in subs), bool, len(subs)),
            np.fromiter((s.found[0] for s in subs), bool, len(subs)),
            np.fromiter((s.ok[0] for s in subs), bool, len(subs)),
            np.stack([s.value[0] for s in subs]))


def check(cell: Cell, win: Window, final, slabs, got):
    kind, kid, op_vals = cell.requests.ops_made(len(win.submitted))
    answered, found, ok, value = got
    cfg = cell.cfg
    return kvref.compare(
        p=cfg.p, k=cfg.k, slots=cfg.slots, loaded_keys=cell.loaded.keys,
        loaded_vals=cell.loaded.vals, q_masks=cell.loaded.q_masks,
        kind=kind, kid=kid, op_vals=op_vals, answered=answered,
        found=found, ok=ok, value=value, slab_ops=slabs[0],
        slab_keys=slabs[1], slab_vals=slabs[2], final_keys=final[0],
        final_vals=final[1], final_valid=final[2])


# ---------------------------------------------------------------------------
# End-to-end metrics (host clock) and the per-layer readers' context
# ---------------------------------------------------------------------------

def end_to_end(cell: Cell, win: Window, setup_s: float
               ) -> Dict[str, float]:
    """Every end-to-end quantity this harness can take; the cell reports
    those ``BENCHMARK.json`` gives it.  Latency runs from a request's
    scheduled arrival (open loop) or its submission (closed loop) to its
    answer, over every request sent in the window."""
    subs = win.submitted
    n = len(subs)
    done_s = np.fromiter((s.done_s for s in subs), np.float64, n)
    done = np.fromiter((s.done for s in subs), bool, n)
    out = {"setup_s": setup_s,
           "ops_per_s": int((done & (done_s <= win.deadline)).sum())
           / (win.deadline - win.t0)}
    if cell.traffic.loop == "open":
        start = win.t0 + cell.requests.arrival_s(np.arange(n))
    else:
        start = np.fromiter((s.submit_s for s in subs), np.float64, n)
    lat = (done_s - start)[done]
    if len(lat):
        out["p50_ms"] = float(np.percentile(lat, 50) * 1e3)
        out["p99_ms"] = float(np.percentile(lat, 99) * 1e3)
    return out


def reader_context(cell: Cell, win: Window, slabs, peak: dict) -> dict:
    s0, s1 = win.slab_range
    ops, ok = slabs[0][s0:s1], slabs[3][s0:s1]
    cfg = cell.cfg
    return {
        "counters": win.counters,
        "trace": win.trace,
        "work": {"slabs": s1 - s0,
                 "live_lanes": int((ops != 0).sum()),
                 "accepted_mutations": int((ok & (ops >= 2)).sum())},
        "geometry": {"p": cfg.p, "k": cfg.k, "slots": cfg.slots,
                     "key_words": cfg.key_words,
                     "val_words": cfg.val_words, "buckets": cfg.buckets},
        "peak": peak,
        "steps": {"max_ms": win.step_max_s * 1e3},
    }


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------

def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def run_cell(spec: dict, workload: str, seed: int, seconds: float,
             trace: bool, *, t_start: float, device, n_devices: int,
             overrides=None,
             wrap_stream=None, donate: bool = True) -> dict:
    """One run of one cell; returns the result line's object."""
    import jax
    entry = cell_entry(spec, workload)
    config = load_config(spec, entry["config"])
    traffic = load_traffic(entry["traffic"])
    peak = peaks_for(device.device_kind) if device.platform == "tpu" else {}
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    cell = build(config, traffic, seed, overrides=overrides,
                 wrap_stream=wrap_stream, donate=donate)
    setup_s = time.perf_counter() - t_start
    log(f"[{workload}] set-up {setup_s:.3f} s: {len(cell.loaded.keys)} "
        f"records into {cell.cfg.buckets} buckets ({cell.spilled} spilled), "
        f"{traffic.pool_ops} ops in the request pool")

    trace_dir = tempfile.mkdtemp(prefix="kvbench_trace_") if trace else None
    try:
        win = run_window(cell, seconds, trace_dir, device.platform)
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    stats = device.memory_stats() or {}
    mem_peak = int(stats.get("peak_bytes_in_use", 0))
    lat = win.lateness_s
    log(f"[{workload}] window {seconds} s: {len(win.submitted)} requests, "
        f"{win.counters['slabs']} slabs, {win.compiles} programs built in "
        f"the window, longest step {win.step_max_s * 1e3:.3f} ms; "
        f"generator late by p99 "
        f"{np.percentile(lat, 99) * 1e3 if len(lat) else 0.0:.3f} ms, max "
        f"{lat.max() * 1e3 if len(lat) else 0.0:.3f} ms")

    final = fetch_final(cell)
    slabs = fetch_slabs(cell)
    ctx = reader_context(cell, win, slabs, peak)
    del cell.server
    got = answers(win)
    checks, facts = check(cell, win, final, slabs, got)
    log(f"[{workload}] reference: {facts}")

    n_ops = len(win.submitted)
    kind = cell.requests.ops_made(n_ops)[0]
    answered, found, ok = got[:3]
    failed = int((~answered | ((kind == kvtraffic.OP_UPDATE) & ~ok)
                  | ((kind == kvtraffic.OP_SEARCH) & ~found)).sum())
    metrics = {}
    if trace:
        for m in cell_metrics(spec, workload, "per_layer"):
            v = load_metric(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        values = end_to_end(cell, win, setup_s)
        for m in cell_metrics(spec, workload, "end_to_end"):
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": n_devices, "memory_peak_bytes": mem_peak}
    result = {"correct": all(v == 0 for v in checks.values()),
              "attempted": n_ops, "failed": failed, "metrics": metrics,
              "device": dev}
    if trace and win.trace is not None:
        dev["busy_s"] = win.trace["busy_s"]
        dev["window_s"] = win.trace["window_s"]
        result["breakdown"] = {"device_ops": win.trace["top_ops"],
                               "idle_gaps": win.trace["idle_gaps"]}
    result["checks"] = {name: {"value": v, "limit": 0}
                        for name, v in checks.items()}
    return result

