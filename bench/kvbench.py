"""The table benchmark's harness: one cell, one run.

A cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``bench/configs/<name>.json``) and a traffic mix
(``bench/traffic/<name>.json``); its metrics are the ``BENCHMARK.json``
entries that list it, and each per-layer metric is read by
``bench/metrics/<name>.py``.  Nothing here names a cell, a configuration
or a metric, so a later change adds any of them as files and entries.

A configuration's keys (``CONFIG_KEYS``): ``table`` (the
``HashTableConfig`` fields), ``recordcount`` and ``load_factor`` (the
bulk load), ``slab_steps``, ``record``, ``guarantees``, ``deployment``
(the chips the cell stands for and how the table is split over them),
``source``, ``why``, ``assumed`` and ``reduced``.  The table's layout
comes from ``table``: ``shards`` 1 is one memory domain on one chip; a
mesh configuration sets ``table.shards`` to its partitions (with
``router``, and ``replica_groups`` where shards have replicas), and its
cell's ``chips`` must equal the devices the table occupies
(``HashTableConfig.mesh_devices``: ``shards``, or the sum of
``replica_groups``).

A run:

1. set-up (``setup_s``): the compile cache, the records, the H3 matrix and
   the requests made from the seed; the bulk load and the served stream,
   through the program's entry points for the table's layout (one domain:
   ``engine.bulk_build`` and ``engine.run_stream`` on the fused Pallas
   kernel, jitted for the one ``[slab_steps, p]`` slab shape, table
   donated; a mesh: ``distributed.make_distributed_bulk_build`` and
   ``make_distributed_stream`` as the program returns them), compiled and
   warmed on NOP slabs; the ``TableServer``;
2. the window: ``--seconds`` of a closed loop (``clients`` each with one
   request outstanding) or an open loop (requests at seeded arrival
   times), through ``TableServer.submit`` / ``TableServer.step``, the
   device profiled when ``trace`` is set.  Every request is one operation
   from the pool made in set-up; nothing is generated in the window, and
   Python's collector is held off it (the harness keeps every request for
   the check, which a server would have freed);
3. the check: once the window has closed and the server has drained, the
   peak device memory is read (the fullest of the cell's devices), the
   table's final plaintext is fetched and the program's state freed; then
   ``kvref.compare`` holds every answer and the final table to the plain
   reference.
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
               "slab_steps", "guarantees", "record", "deployment", "assumed",
               "reduced", "why"}


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


def cell_config(spec: dict, cell: str, root: str = REPO) -> dict:
    """The configuration of ``cell``, refused when the cell's ``chips``
    differ from the devices its table occupies."""
    entry = cell_entry(spec, cell)
    config = load_config(spec, entry["config"], root)
    devices = table_config(config).mesh_devices
    if entry["chips"] != devices:
        raise ValueError(
            f"{cell}: the cell asks for {entry['chips']} chips but "
            f"configuration {entry['config']!r} lays its table over "
            f"mesh_devices = {devices}")
    return config


# ---------------------------------------------------------------------------
# Set-up
# ---------------------------------------------------------------------------

class Capture:
    """The stream the server is given: the served stream's entry points,
    each dispatched slab's inputs and results kept for the check.

    It forwards the stream's ``router``, ``slack``, ``cfg``, ``measure`` and
    ``plan``, so ``TableServer`` sees a bounded router as such, keeps its
    plan cache and hands each slab's plan to :meth:`dispatch`.  ``wrap``
    (the control and the fault tests) puts a changed stream in the served
    one's place on both routes, ``__call__`` and ``dispatch``."""
    FORWARDED = ("router", "slack", "cfg", "measure", "plan")

    def __init__(self, stream: Callable, wrap: Optional[Callable] = None):
        self.stream = stream
        self.slabs: list = []
        self._plan = None
        wrap = wrap or (lambda f: f)
        self._call = wrap(stream)
        self._planned = wrap(lambda t, o, k, v: stream.dispatch(
            t, o, k, v, self._plan))

    def __getattr__(self, name):
        if name in Capture.FORWARDED:
            return getattr(self.stream, name)
        raise AttributeError(name)

    def _keep(self, ops, keys, vals, out):
        self.slabs.append((ops, keys, vals, out[1]))
        return out

    def __call__(self, table, ops, keys, vals):
        return self._keep(ops, keys, vals,
                          self._call(table, ops, keys, vals))

    def dispatch(self, table, ops, keys, vals, plan):
        self._plan = plan
        return self._keep(ops, keys, vals,
                          self._planned(table, ops, keys, vals))


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


def _nop_slab(cfg, T: int):
    import jax.numpy as jnp
    N = cfg.queries_per_step
    return (jnp.zeros((T, N), jnp.int32),
            jnp.zeros((T, N, cfg.key_words), jnp.uint32),
            jnp.zeros((T, N, cfg.val_words), jnp.uint32))


def _one_domain(cfg, loaded, T: int, donate: bool):
    """One memory domain: ``init_table``, ``engine.bulk_build`` and the
    fused ``engine.run_stream``, jitted with the table donated, compiled
    and run once on a NOP slab."""
    import jax
    import jax.numpy as jnp
    from repro.core import engine, init_table
    table = init_table(cfg, jax.random.key(0))
    table = dataclasses.replace(table, q_masks=jnp.asarray(loaded.q_masks))
    table, report = engine.bulk_build(table, jnp.asarray(loaded.keys),
                                      jnp.asarray(loaded.vals))
    spilled = int(np.asarray(report.spilled).sum())
    stream = jax.jit(functools.partial(engine.run_stream, backend="pallas"),
                     donate_argnums=(0,) if donate else ())
    table, res = stream(table, *_nop_slab(cfg, T))  # compile + warm
    jax.block_until_ready((table, res))
    return table, stream, spilled


def _mesh(cfg, loaded, T: int, requests, scfg):
    """A table over ``cfg.mesh_devices`` devices: ``make_ht_mesh``,
    ``init_distributed_table`` with the seed's H3 matrix replicated,
    ``make_distributed_bulk_build`` over the records laid out as one
    ``[1, N]`` row in load order (each device's lane block a contiguous
    run, padding lanes not ``live``), and ``make_distributed_stream`` as
    the program returns it.

    The stream is warmed on NOP slabs, which write nothing: a bounded
    router builds one program per routed shape, so the cell's request pool
    is packed into slabs as the server packs a full queue and planned by a
    plan cache of the server's settings, and each shape those plans take is
    run once."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import distributed as dist
    from repro.serving.engine import TableServer
    from repro.serving.serve_loop import (PlanCache, SlabQueue, SlabRequest,
                                          measure_loads_host, op_mix_bucket)
    D = cfg.mesh_devices
    mesh = dist.make_ht_mesh(D)
    table = dist.init_distributed_table(cfg, jax.random.key(0), mesh)
    table = dataclasses.replace(table, q_masks=jax.device_put(
        jnp.asarray(loaded.q_masks), NamedSharding(mesh, P())))

    R = len(loaded.keys)
    n = -(-R // D)
    keys = np.zeros((D * n, cfg.key_words), np.uint32)
    vals = np.zeros((D * n, cfg.val_words), np.uint32)
    live = np.zeros(D * n, bool)
    keys[:R], vals[:R], live[:R] = loaded.keys, loaded.vals, True
    lanes = NamedSharding(mesh, P(None, "ht"))
    table, report = dist.make_distributed_bulk_build(mesh, cfg)(
        table, jax.device_put(keys[None], lanes),
        jax.device_put(vals[None], lanes), jax.device_put(live[None], lanes))
    spilled = int((np.asarray(report.spilled)[0] & live).sum())

    stream = dist.make_distributed_stream(mesh, cfg)
    nop = _nop_slab(cfg, T)
    if getattr(stream, "router", None) != "bounded":
        table, res = stream(table, *nop)
        jax.block_until_ready((table, res))
        return table, stream, spilled
    N = cfg.queries_per_step
    queue = SlabQueue(T, N, cfg.key_words, cfg.val_words,
                      nsq_lanes=TableServer._nsq_mask(cfg))
    kind, _, vals = requests.ops_made(requests.t.pool_ops)
    queue.submit(SlabRequest(rid=0, ops=kind, keys=requests.keys, vals=vals))
    cache = PlanCache(cfg, plans=scfg.plan_cache_plans, slack=stream.slack)
    shapes = {}
    while queue.pending_requests:
        slab = queue.next_slab()
        loads, pair = measure_loads_host(cfg, loaded.q_masks, slab.keys,
                                         slab.ops)
        plan, _ = cache.lookup(loads, pair, op_mix_bucket(slab.ops),
                               n_local=N // D)
        shapes.setdefault((plan.pair_capacity, plan.routed_width,
                           plan.routed_steps, plan.carried_lanes), plan)
    for plan in shapes.values():
        table, res = stream.dispatch(table, *nop, plan)
        jax.block_until_ready((table, res))
    return table, stream, spilled


def build(config: dict, traffic: kvtraffic.Traffic, seed: int, *,
          overrides: Optional[dict] = None,
          wrap_stream: Optional[Callable] = None, donate: bool = True
          ) -> Cell:
    """Everything before the window.  ``overrides`` replaces table fields
    (the tests' tiny tables); ``wrap_stream`` puts a changed stream in the
    served one's place (the control and the fault tests).  ``table.shards``
    picks the layout: 1 is one memory domain, more a mesh."""
    from repro.serving.engine import ServeConfig, TableServer

    cfg = table_config(config, overrides)
    records = int(config["load_factor"] * cfg.buckets * cfg.slots)
    if not overrides and records != config["recordcount"]:
        raise ValueError(f"recordcount {config['recordcount']} is not "
                         f"load_factor x buckets x slots = {records}")
    loaded = kvtraffic.make_loaded(records, cfg.index_bits, cfg.key_words,
                                   cfg.val_words, cfg.slots, seed)
    requests = kvtraffic.RequestStream(traffic, loaded, seed)
    T = config["slab_steps"]
    scfg = ServeConfig(slab_steps=T, geometry_replan=False)
    if cfg.shards > 1:
        table, stream, spilled = _mesh(cfg, loaded, T, requests, scfg)
    else:
        table, stream, spilled = _one_domain(cfg, loaded, T, donate)
    capture = Capture(stream, wrap_stream)
    server = TableServer(cfg, table, capture, scfg)

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
    counters: Dict[str, int]        # change of server.stats()'s integers
    slab_range: tuple               # captured slabs dispatched in the window
    lateness_s: np.ndarray          # open loop: submit time - arrival time
    step_max_s: float               # longest TableServer.step() in the window
    compiles: int                   # programs built inside the window
    trace: Optional[dict] = None    # trace_reduce.summarize of the window


def counters(stats: dict, prefix: str = "") -> Dict[str, int]:
    """Every integer of ``TableServer.stats()``, nested keys joined by
    ``.`` (``plan_cache.misses``); flags, ratios and lists are left out."""
    out: Dict[str, int] = {}
    for key, v in stats.items():
        if isinstance(v, dict):
            out.update(counters(v, f"{prefix}{key}."))
        elif isinstance(v, (int, np.integer)) and not isinstance(v, bool):
            out[prefix + key] = int(v)
    return out


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
            c0, s0 = counters(srv.stats()), len(cell.capture.slabs)
            t0 = time.perf_counter()
            with _span()("bench.window"):
                late = drive(cell, t0, seconds, submit, steps, lateness)
            c1, s1 = counters(srv.stats()), len(cell.capture.slabs)
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
                  {k: v - c0.get(k, 0) for k, v in c1.items()}, (s0, s1),
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
        final_vals=final[1], final_valid=final[2], devices=cfg.mesh_devices)


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
                     "val_words": cfg.val_words, "buckets": cfg.buckets,
                     "shards": cfg.shards, "devices": cfg.mesh_devices},
        "peak": peak,
        "steps": {"max_ms": win.step_max_s * 1e3},
        "window": {"compiles": win.compiles},
    }


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------

def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def memory_peaks(devices) -> List[int]:
    """``peak_bytes_in_use`` of each device (0 where the backend keeps no
    count)."""
    return [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in devices]


def run_cell(spec: dict, workload: str, seed: int, seconds: float,
             trace: bool, *, t_start: float, devices: list,
             overrides=None, wrap_stream=None, donate: bool = True,
             root: str = REPO) -> dict:
    """One run of one cell on ``devices`` (every device JAX sees; the cell
    uses the first ``chips``); returns the result line's object.  The
    cell's files are read from the checkout at ``root``."""
    import jax
    bench = os.path.join(root, "bench")
    entry = cell_entry(spec, workload)
    config = cell_config(spec, workload, root)
    traffic = load_traffic(entry["traffic"], bench)
    device = devices[0]
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
    peaks = memory_peaks(devices[:entry["chips"]])
    lat = win.lateness_s
    log(f"[{workload}] window {seconds} s: {len(win.submitted)} requests, "
        f"{win.counters['slabs']} slabs, {win.compiles} programs built in "
        f"the window, longest step {win.step_max_s * 1e3:.3f} ms; "
        f"generator late by p99 "
        f"{np.percentile(lat, 99) * 1e3 if len(lat) else 0.0:.3f} ms, max "
        f"{lat.max() * 1e3 if len(lat) else 0.0:.3f} ms; memory peak by "
        f"device {peaks}")

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
            v = load_metric(m["name"], bench).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        values = end_to_end(cell, win, setup_s)
        for m in cell_metrics(spec, workload, "end_to_end"):
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": len(devices), "memory_peak_bytes": max(peaks)}
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

