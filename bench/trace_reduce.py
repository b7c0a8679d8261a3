"""Reduce a ``jax.profiler`` trace (``.xplane.pb``) to the benchmark's
device numbers: time per device operation, the device's busy time (the
union of its operations' intervals) and its idle gaps, all inside the
window the harness marks with its ``bench.window`` host span.

Device operations are the events of the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane.  On the CPU, where the tests record a trace, the
operations are the XLA events of the ``/host:CPU`` plane's client threads.
Each idle gap is labelled by the harness span (``bench.step``,
``bench.submit``, ``bench.wait``) that covers its middle, so the host's
share of the idle time shows.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Tuple

WINDOW_SPAN = "bench.window"
_TPU_PLANE = re.compile(r"^/device:TPU:\d+$")
_CPU_OP_LINE = "tf_XLAPjRtCpuClient"
_CPU_NOT_OPS = ("ThreadpoolListener", "end: ")

Event = Tuple[str, float, float]          # name, start ns, end ns


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(log_dir: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(find_xplane(log_dir))


def _events(line) -> List[Event]:
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events]


def op_name(event_name: str) -> str:
    """An operation's own name: a TPU op event is named by its HLO text,
    ``%copy.12 = u32[...] copy(...)``, whose operands name other ops."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def device_ops(pd, platform: str) -> List[List[Event]]:
    """Per device, its operations' events."""
    out = []
    for plane in pd.planes:
        if platform == "tpu" and _TPU_PLANE.match(plane.name):
            evs = [(op_name(n), s, e) for line in plane.lines
                   if line.name == "XLA Ops" for n, s, e in _events(line)]
            out.append(evs)
        elif platform == "cpu" and plane.name == "/host:CPU":
            evs = [e for line in plane.lines
                   if line.name.startswith(_CPU_OP_LINE)
                   for e in _events(line)
                   if not e[0].startswith(_CPU_NOT_OPS) and e[2] > e[1]]
            out.append(evs)
    return out


def host_spans(pd, prefix: str = "bench.") -> List[Event]:
    return [e for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in _events(line)
            if e[0].startswith(prefix)]


def merge(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """The union of ``[start, end)`` intervals, as sorted disjoint ones."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(events: List[Event], lo: float, hi: float) -> List[Event]:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def summarize(pd, platform: str, top: int = 10) -> Dict:
    """``window_s``, ``busy_s`` (averaged over devices), ``op_seconds``
    (summed over devices, by operation name), ``top_ops`` and the longest
    ``idle_gaps`` as ``[label, seconds]`` lists."""
    spans = host_spans(pd)
    windows = [e for e in spans if e[0] == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace has no {WINDOW_SPAN!r} span")
    _, lo, hi = max(windows, key=lambda e: e[2] - e[1])
    devices = device_ops(pd, platform)
    if not devices:
        raise ValueError(f"the trace has no {platform} device operations")
    op_ns: Dict[str, float] = defaultdict(float)
    busy_ns, gaps = 0.0, []
    labels = sorted((s, e, n) for n, s, e in spans if n != WINDOW_SPAN)
    for evs in devices:
        evs = clip(evs, lo, hi)
        for name, s, e in evs:
            op_ns[name] += e - s
        busy = merge([(s, e) for _, s, e in evs])
        busy_ns += sum(e - s for s, e in busy)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 > g0:
                gaps.append((g1 - g0, _label(labels, (g0 + g1) / 2)))
    gaps.sort(reverse=True)
    ranked = sorted(op_ns.items(), key=lambda kv: -kv[1])
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_ns * 1e-9 / len(devices),
        "op_seconds": {n: v * 1e-9 for n, v in op_ns.items()},
        "top_ops": [[n, v * 1e-9] for n, v in ranked[:top]],
        "idle_gaps": [[lab, ns * 1e-9] for ns, lab in gaps[:top]],
    }


def _label(spans, t: float) -> str:
    """The harness span around time ``t`` (the harness's spans inside the
    window follow one another and do not nest)."""
    i = bisect.bisect_right(spans, (t, float("inf"), "")) - 1
    if i >= 0 and spans[i][1] >= t:
        return spans[i][2]
    return "host:other"


def kernel_seconds(op_seconds: Dict[str, float], kernel: str) -> float:
    """Device seconds of the operations that run the Pallas kernel
    ``kernel``: those whose name starts with it (on the v5e the kernel's
    custom call is ``xor_stream_pallas.<n>``)."""
    return sum(v for n, v in op_seconds.items() if n.startswith(kernel))
