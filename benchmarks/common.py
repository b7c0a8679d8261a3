"""Benchmark plumbing: wall-clock timing + CSV rows (name,us_per_call,derived)."""
from __future__ import annotations

import os
import subprocess
import sys
import time
from typing import Callable, List, Sequence

import numpy as np
import jax

ROWS: List[str] = []
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_fake_device_child(script: str, flags: Sequence[str] = (),
                          devices: int = 8, capture: bool = False):
    """Re-exec ``script --child *flags`` on ``devices`` fake CPU devices (a
    mesh benchmark's measurement half), keeping this process's own device
    view.  Refuses on a TPU backend: this process then holds the chip, so a
    child could neither take it nor stand in for it."""
    if jax.default_backend() == "tpu":
        raise RuntimeError(
            f"{os.path.basename(script)} measures its mesh on {devices} fake "
            f"CPU devices in a child process; on a TPU host this process "
            f"holds the chip — run it with JAX_PLATFORMS=cpu, or use "
            f"chip_smoke.py --chips 4 for the real four-chip mesh")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_host_"
                        f"platform_device_count={devices}").strip()
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(ROOT, "src"), ROOT, env.get("PYTHONPATH", "")])
    return subprocess.run([sys.executable, os.path.abspath(script), "--child",
                           *flags], env=env, cwd=ROOT,
                          capture_output=capture, text=capture)


def bench(fn: Callable, iters: int = 20, warmup: int = 3) -> float:
    """Median wall time per call in microseconds (blocks on jax outputs).
    For *comparing* implementations use :func:`bench_group` instead."""
    for _ in range(warmup):
        out = fn()
        jax.block_until_ready(out)
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn()
        jax.block_until_ready(out)
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts) * 1e6)


def mixed_stream(cfg, steps: int, seed: int = 0):
    """The paper's uniform stimulus, shared by every throughput benchmark:
    [T, N] tensors of 50% search / 50% insert-update ops with random keys
    and values (jnp arrays, ready for run_stream)."""
    import jax.numpy as jnp
    from repro.core import OP_INSERT, OP_SEARCH
    rng = np.random.default_rng(seed)
    N = cfg.queries_per_step
    ops = rng.choice([OP_SEARCH, OP_INSERT], size=(steps, N)).astype(np.int32)
    keys = rng.integers(1, 2 ** 32, size=(steps, N, cfg.key_words),
                        dtype=np.uint32)
    vals = rng.integers(1, 2 ** 32, size=(steps, N, cfg.val_words),
                        dtype=np.uint32)
    return jnp.array(ops), jnp.array(keys), jnp.array(vals)


def bench_group(fns: dict, iters: int = 9, warmup: int = 2) -> dict:
    """Paired best-of-N timing for *comparing* implementations: every round
    times each fn once (round-robin), so host-load drift hits all candidates
    equally instead of whichever one ran during a contended window.  Returns
    {name: best wall time per call in microseconds}."""
    for fn in fns.values():
        for _ in range(warmup):
            jax.block_until_ready(fn())
    best = {name: float("inf") for name in fns}
    for _ in range(iters):
        for name, fn in fns.items():
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            best[name] = min(best[name], time.perf_counter() - t0)
    return {name: t * 1e6 for name, t in best.items()}


def row(name: str, us_per_call: float, derived: str) -> None:
    line = f"{name},{us_per_call:.1f},{derived}"
    ROWS.append(line)
    print(line, flush=True)
