#!/usr/bin/env python3
"""The program's own spans and scopes in a ``jax.profiler`` trace, and a
traced run of one cell with them recorded.

    python3 bench/spans.py --workload <cell> --seed <n> --seconds <s> \\
        [--spans 0|1] [--out <file.json>]

The program marks its host work with ``kv.*`` spans (``repro.tracing``:
``kv.submit``, ``kv.step`` and inside it ``kv.pack``, ``kv.dispatch``,
``kv.retire`` with ``kv.retire.wait``, ``kv.migrate``) and the stream seam's
device work with ``kv.*`` named scopes (``kv.h3``, ``kv.prepass``,
``kv.table_pack``, ``kv.table_unpack``, ``kv.unpermute``) around the fused
kernel ``xor_stream``.  :func:`reduce_trace` turns one trace into

- ``span_self_s`` / ``span_count``: by ``kv.*`` name, inside the
  harness's ``bench.window``; a span's self time is its duration less the
  part its child spans cover (spans of one thread nest);
- ``idle_by_span``: the device's idle time in the window, split by the
  innermost host span open at each instant (a ``kv.*`` span, else a
  ``bench.*`` one, else ``host:other``); it sums to ``window_s - busy_s``;
- ``scope_seconds``: device seconds by scope.  An operation's scope comes
  from the compiled program's ``op_name`` metadata (:func:`scope_map`);
  the TPU's trace events carry only the operation's name.

:func:`layer_numbers` gives the per-slab and per-request quantities read
from them.  The command runs one cell as ``bench/run.py --trace 1`` does
(``kvbench.build``, ``kvbench.run_window``), with the spans recorded when
``--spans 1``, and prints one JSON line: the window's ``ops_per_s``, the
reduction, the layer numbers and the longest ``kv.step`` with its
children.  It runs no check; ``bench/run.py`` does.
"""
from __future__ import annotations

import argparse
import bisect
import json
import os
import re
import shutil
import sys
import tempfile
import time
from collections import defaultdict
from typing import Dict, List, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(BENCH)
for _p in (os.path.join(REPO, "src"), BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import trace_reduce  # noqa: E402

PREFIX = "kv."
KERNEL = "xor_stream"
OTHER = "host:other"


# ---------------------------------------------------------------------------
# Host spans
# ---------------------------------------------------------------------------

def _nest(spans):
    """``(name, start, end, parent index)`` of spans that nest, sorted by
    start (an outer span before the inner one that starts with it)."""
    out, stack = [], []
    for n, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        while stack and out[stack[-1]][2] <= s:
            stack.pop()
        out.append((n, s, e, stack[-1] if stack else None))
        stack.append(len(out) - 1)
    return out


def span_self(spans, lo: float, hi: float):
    """Self seconds and count of each ``kv.*`` span name over the spans
    that overlap ``[lo, hi]`` (clipped to it)."""
    nested = _nest(trace_reduce.clip(spans, lo, hi))
    self_ns = [e - s for _, s, e, _ in nested]
    for n, s, e, parent in nested:
        if parent is not None:
            self_ns[parent] -= e - s
    self_s: Dict[str, float] = defaultdict(float)
    count: Dict[str, int] = defaultdict(int)
    for (n, _, _, _), v in zip(nested, self_ns):
        if n.startswith(PREFIX):
            self_s[n] += v * 1e-9
            count[n] += 1
    return dict(self_s), dict(count)


def innermost(spans, lo: float, hi: float):
    """``[lo, hi]`` cut into ``(start, end, label)`` pieces, each labelled
    by the innermost span open in it (``host:other`` where none is)."""
    pieces, stack, t = [], [], lo
    marks = []
    for i, (n, s, e) in enumerate(trace_reduce.clip(spans, lo, hi)):
        marks += [(s, 1, -e, i, n), (e, 0, 0.0, i, n)]
    for x, opens, _, i, n in sorted(marks):
        if x > t:
            pieces.append((t, x, stack[-1][1] if stack else OTHER))
            t = x
        if opens:
            stack.append((i, n))
        else:
            stack.remove((i, n))
    if hi > t:
        pieces.append((t, hi, OTHER))
    return pieces


def idle_by_label(busy, pieces, lo: float, hi: float) -> Dict[str, float]:
    """Idle nanoseconds of one device (``busy``: its sorted disjoint busy
    intervals) in ``[lo, hi]``, by the label of the piece they fall in."""
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    idle = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    out: Dict[str, float] = defaultdict(float)
    starts = [p[0] for p in pieces]
    for a, b in idle:
        j = max(bisect.bisect_right(starts, a) - 1, 0)
        while j < len(pieces) and pieces[j][0] < b:
            s, e, label = pieces[j]
            if min(b, e) > max(a, s):
                out[label] += min(b, e) - max(a, s)
            j += 1
    return out


# ---------------------------------------------------------------------------
# Device scopes
# ---------------------------------------------------------------------------

_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) ")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%([\w.\-]+) = ")
_OPERAND = re.compile(r"%([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def scope_map(hlo_text: str) -> Dict[str, str]:
    """``{instruction: scope}`` of a compiled program's text: the first
    ``kv.*`` component of the instruction's ``op_name``, or ``xor_stream``
    for the kernel's own operations.  An instruction with neither (the
    layout copies and bitcasts XLA adds) takes the ``kv.*`` scope of an
    operand, else of a user, through others with none."""
    scope: Dict[str, Optional[str]] = {}
    comp_of: Dict[str, str] = {}
    operands: Dict[str, List[str]] = {}
    comp = ""
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m and line.rstrip().endswith("{"):
            comp = m.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        name = m.group(1)
        op = _OP_NAME.search(line)
        parts = op.group(1).split("/") if op else []
        own = [p for p in parts if p.startswith(PREFIX) or p == KERNEL]
        scope[name] = own[0] if own else None
        comp_of[name] = comp
        operands[name] = _OPERAND.findall(
            line[m.end():].split(", metadata=")[0])
    near: Dict[str, List[str]] = defaultdict(list)    # operands, then users
    for n, ops in operands.items():
        ops = [o for o in ops if comp_of.get(o) == comp_of[n]]
        near[n][:0] = ops
        for o in ops:
            near[o].append(n)
    changed = True
    while changed:
        changed = False
        for n in scope:
            if scope[n] is None:
                got = [scope[o] for o in near[n]
                       if (scope[o] or "").startswith(PREFIX)]
                if got:
                    scope[n] = got[0]
                    changed = True
    return {n: s for n, s in scope.items() if s is not None}


def scope_seconds(op_seconds: Dict[str, float],
                  scope_of: Dict[str, str]) -> Dict[str, float]:
    """Device seconds by scope; operations the map does not know are
    ``unscoped``."""
    out: Dict[str, float] = defaultdict(float)
    for name, v in op_seconds.items():
        out[scope_of.get(name, "unscoped")] += v
    return dict(out)


# ---------------------------------------------------------------------------
# One trace
# ---------------------------------------------------------------------------

def reduce_trace(pd, platform: str,
                 scope_of: Optional[Dict[str, str]] = None) -> Dict:
    """The program's spans and scopes in the trace's ``bench.window``
    (module docstring); ``scope_seconds`` needs the compiled program's
    ``scope_of`` map."""
    base = trace_reduce.summarize(pd, platform)
    bench = trace_reduce.host_spans(pd)
    _, lo, hi = max((e for e in bench if e[0] == trace_reduce.WINDOW_SPAN),
                    key=lambda e: e[2] - e[1])
    kv = trace_reduce.host_spans(pd, PREFIX)
    self_s, count = span_self(kv, lo, hi)
    pieces = innermost(kv + [e for e in bench
                             if e[0] != trace_reduce.WINDOW_SPAN], lo, hi)
    devices = trace_reduce.device_ops(pd, platform)
    idle: Dict[str, float] = defaultdict(float)
    for evs in devices:
        busy = trace_reduce.merge([(s, e) for _, s, e in
                                   trace_reduce.clip(evs, lo, hi)])
        for label, ns in idle_by_label(busy, pieces, lo, hi).items():
            idle[label] += ns * 1e-9 / len(devices)
    out = {"window_s": base["window_s"], "busy_s": base["busy_s"],
           "span_self_s": self_s, "span_count": count,
           "idle_by_span": dict(idle),
           "longest_step": longest_step(kv, lo, hi)}
    if scope_of is not None:
        out["scope_seconds"] = scope_seconds(base["op_seconds"], scope_of)
        out["kernel_s"] = trace_reduce.kernel_seconds(base["op_seconds"],
                                                      KERNEL)
        out["unscoped_ops"] = sorted(
            ([n, v] for n, v in base["op_seconds"].items()
             if n not in scope_of), key=lambda x: -x[1])[:10]
    return out


def longest_step(kv_spans, lo: float, hi: float) -> Optional[Dict]:
    """The longest ``kv.step`` inside the window: its milliseconds and
    self milliseconds, and ``[name, ms, self ms]`` of its direct children
    in order."""
    nested = _nest([e for e in kv_spans if lo <= e[1] and e[2] <= hi])
    steps = [i for i, x in enumerate(nested) if x[0] == "kv.step"]
    if not steps:
        return None
    ms = [(e - s) * 1e-6 for _, s, e, _ in nested]
    inner = [0.0] * len(nested)
    for i, (_, _, _, p) in enumerate(nested):
        if p is not None:
            inner[p] += ms[i]
    top = max(steps, key=lambda i: ms[i])
    kids = [[x[0], ms[i], ms[i] - inner[i]] for i, x in enumerate(nested)
            if x[3] == top]
    return {"ms": ms[top], "self_ms": ms[top] - inner[top], "children": kids}


def layer_numbers(red: Dict, slabs: int) -> Dict[str, float]:
    """The layer quantities of one reduced window, ``slabs`` being the
    slabs dispatched in it; a quantity with nothing to read is left out."""
    self_s, count = red["span_self_s"], red["span_count"]
    out = {}
    if count.get("kv.submit"):
        out["submit_us_per_op"] = (self_s["kv.submit"] * 1e6
                                   / count["kv.submit"])
    if slabs:
        for metric, span in (("pack_ms_per_slab", "kv.pack"),
                             ("dispatch_ms_per_slab", "kv.dispatch"),
                             ("retire_ms_per_slab", "kv.retire")):
            if span in self_s:
                out[metric] = self_s[span] * 1e3 / slabs
        sc = red.get("scope_seconds", {})
        repack = [sc[s] for s in ("kv.table_pack", "kv.table_unpack")
                  if s in sc]
        if repack:
            out["table_repack_ms_per_slab"] = sum(repack) * 1e3 / slabs
    idle = red["idle_by_span"]
    total = sum(idle.values())
    if total > 0 and count:
        out["idle_in_server_share"] = 100.0 * sum(
            v for k, v in idle.items() if k.startswith(PREFIX)) / total
    return out


# ---------------------------------------------------------------------------
# A traced run of one cell
# ---------------------------------------------------------------------------

def served_hlo(cell) -> str:
    """The compiled text of the served stream at the cell's slab shape."""
    import jax.numpy as jnp
    stream = cell.capture.stream
    srv = cell.server
    T, N = srv.scfg.slab_steps, cell.cfg.queries_per_step
    nop = (jnp.zeros((T, N), jnp.int32),
           jnp.zeros((T, N, cell.cfg.key_words), jnp.uint32),
           jnp.zeros((T, N, cell.cfg.val_words), jnp.uint32))
    return stream.lower(srv.table, *nop).compile().as_text()


def traced_run(spec: dict, workload: str, seed: int, seconds: float,
               spans: bool, platform: str, t_start: float,
               overrides: Optional[dict] = None) -> Dict:
    """One traced window of ``workload``, the program's spans recorded when
    ``spans``; returns the command's result object."""
    import kvbench
    from repro import tracing
    entry = kvbench.cell_entry(spec, workload)
    cell = kvbench.build(kvbench.load_config(spec, entry["config"]),
                         kvbench.load_traffic(entry["traffic"]), seed,
                         overrides=overrides)
    scope_of = scope_map(served_hlo(cell))
    setup_s = time.perf_counter() - t_start
    trace_dir = tempfile.mkdtemp(prefix="kvbench_spans_")
    if spans:
        tracing.enable()
    try:
        win = kvbench.run_window(cell, seconds, trace_dir, platform)
        red = reduce_trace(trace_reduce.load(trace_dir), platform, scope_of)
    finally:
        tracing.disable()
        shutil.rmtree(trace_dir, ignore_errors=True)
    slabs = win.slab_range[1] - win.slab_range[0]
    return {"workload": workload, "seed": seed, "spans": spans,
            "setup_s": setup_s,
            "ops_per_s": kvbench.end_to_end(cell, win, 0.0)["ops_per_s"],
            "requests": len(win.submitted), "slabs": slabs,
            "step_max_ms": win.step_max_s * 1e3,
            "idle_gaps": win.trace["idle_gaps"],
            "top_ops": win.trace["top_ops"],
            "layer": layer_numbers(red, slabs), **red}


def main(argv=None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--spans", type=int, choices=(0, 1), default=1)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    import kvbench
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if jax.devices()[0].platform != "tpu":
        print("bench/spans.py: no TPU found", file=sys.stderr)
        return 1
    r = traced_run(kvbench.load_spec(), args.workload, args.seed,
                   args.seconds, bool(args.spans), "tpu", t_start)
    step = r["longest_step"]
    if step:
        print(f"[{args.workload}] longest kv.step {step['ms']:.3f} ms "
              f"(self {step['self_ms']:.3f}): " + ", ".join(
                  f"{n} {v:.3f} (self {own:.3f})"
                  for n, v, own in step["children"]), file=sys.stderr)
    for label, s in sorted(r["idle_by_span"].items(), key=lambda x: -x[1]):
        print(f"[{args.workload}] idle under {label}: {s:.6f} s",
              file=sys.stderr)
    line = json.dumps(r)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
