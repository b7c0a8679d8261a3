"""Roofline analysis (deliverable g): three terms per (arch x shape) from the
single-pod dry-run artifacts.

  compute_s    = FLOPs / (chips * 197e12)       [bf16 peak, v5e]
  memory_s     = HLO bytes-accessed per device / 819e9
  collective_s = collective bytes per device / 50e9   [1 ICI link worst-case]

FLOPs sources: ``hlo`` = compiled cost_analysis (NOTE: jax.lax.scan bodies are
counted ONCE, not x trip-count — an undercount for deep stacks); ``model`` =
analytic MODEL_FLOPS (6·N_active·D for train, 2·N_active·D prefill/decode,
plus quadratic attention / recurrent-state terms).  The compute term uses
max(hlo x chips, model); the ratio model/hlo is reported per cell.

Also reports measured-vs-modeled for the fused stream kernel: every
BENCH_stream.json row is re-derived from ``perfmodel.stream_modeled_mops``
(commit-cost + table-traffic terms) at the benchmark's config, for each
measured column (scanned ~ serial commit, fused).
Off-TPU the measurement is interpret-mode CPU, so the interesting number is
the RELATIVE shape (fused/scanned ratios), not the absolute gap —
both are printed.  The routed distributed stream gets the same treatment
(BENCH_distributed.json x ``perfmodel.sharded_stream_modeled_mops`` /
``replicated_read_mops``), including the 2-D replication A/B with its
replica-broadcast copy factor.  Likewise for the continuous-batching serve
loop: every
BENCH_serve.json mode is re-derived from ``perfmodel.serve_loop_modeled``
(plan-cache hit rate -> amortized planning, slab padding, double-buffer
overlap), comparing measured and modeled MOPS and p50.

Writes experiments/roofline.csv and prints the table.
"""
from __future__ import annotations

import glob
import json
import os
from typing import Dict

from repro.configs import get_config
from repro.launch.shapes import SHAPES
from repro.models.model_config import (ModelConfig, attn_kinds, layer_kinds,
                                       moe_mask)

PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_BW = 50e9


def model_flops(cfg: ModelConfig, shape_name: str) -> float:
    sh = SHAPES[shape_name]
    B, S = sh["batch"], sh["seq"]
    kind = sh["kind"]
    n_active = cfg.param_count(active_only=True)
    kinds = layer_kinds(cfg)
    ak = attn_kinds(cfg)
    hd = cfg.resolved_head_dim
    H = cfg.n_heads

    def attn_quad(tokens_q, tokens_k, mult):
        """2-FLOP MACs for qk^T + av per attention layer."""
        total = 0.0
        for i, k in enumerate(kinds):
            if k != "attn":
                continue
            Sk = min(tokens_k, cfg.sliding_window) if ak[i] == "local" \
                else tokens_k
            if cfg.use_mla:
                qk, vd = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim,
                          cfg.v_head_dim)
            else:
                qk = vd = hd
            total += mult * 2.0 * B * tokens_q * Sk * H * (qk + vd)
        return total

    def recur(tokens, mult):
        total = 0.0
        di = cfg.d_inner
        for k in kinds:
            if k == "mamba":
                total += mult * 6.0 * B * tokens * di * cfg.ssm_state_dim
            elif k == "mlstm":
                dh = di // max(H, 1)
                total += mult * 6.0 * B * tokens * di * dh
            elif k == "slstm":
                dh = cfg.d_model // max(H, 1)
                total += mult * 8.0 * B * tokens * cfg.d_model * dh
        return total

    if kind == "train":
        return (6.0 * n_active * B * S + attn_quad(S, S, 3.0)
                + recur(S, 3.0))
    if kind == "prefill":
        return (2.0 * n_active * B * S + attn_quad(S, S, 1.0)
                + recur(S, 1.0))
    # decode: one token against S cache
    return (2.0 * n_active * B + attn_quad(1, S, 1.0) + recur(1, 1.0))


def analyze(dryrun_dir: str = "experiments/dryrun",
            mesh: str = "pod") -> list:
    rows = []
    for path in sorted(glob.glob(os.path.join(dryrun_dir,
                                              f"*__{mesh}.json"))):
        rec = json.load(open(path))
        if rec.get("status") != "ok":
            rows.append(dict(arch=rec["arch"], shape=rec["shape"],
                             status=rec.get("error", "error")))
            continue
        chips = rec["n_devices"]
        cfg = get_config(rec["arch"])
        m_flops = model_flops(cfg, rec["shape"])
        hlo_flops_total = rec["flops_per_device"] * chips
        flops = max(m_flops, hlo_flops_total)
        compute_s = flops / (chips * PEAK_FLOPS)
        memory_s = rec["bytes_accessed_per_device"] / HBM_BW
        coll_b = sum(rec["collective_bytes_per_device"].values())
        collective_s = coll_b / ICI_BW
        terms = {"compute": compute_s, "memory": memory_s,
                 "collective": collective_s}
        dominant = max(terms, key=terms.get)
        bound_s = max(terms.values())
        rows.append(dict(
            arch=rec["arch"], shape=rec["shape"], status="ok", chips=chips,
            compute_s=compute_s, memory_s=memory_s, collective_s=collective_s,
            dominant=dominant,
            model_flops=m_flops, hlo_flops=hlo_flops_total,
            model_over_hlo=(m_flops / hlo_flops_total
                            if hlo_flops_total else float("inf")),
            roofline_frac=compute_s / bound_s if bound_s else 0.0,
            mem_temp_gb=rec["memory"]["temp_bytes"] / 1e9,
            mem_args_gb=rec["memory"]["argument_bytes"] / 1e9,
        ))
    return rows


def stream_measured_vs_modeled(path: str = "BENCH_stream.json") -> list:
    """measured-vs-modeled rows for the fused stream kernel
    (BENCH_stream.json x perfmodel.stream_modeled_mops)."""
    from repro.core.config import HashTableConfig
    from repro.core.perfmodel import MIX_DEFAULT, stream_modeled_mops
    if not os.path.exists(path):
        return []
    bench = json.load(open(path))
    # the bench records its table geometry so the model can't desync from it
    table = bench.get("table", dict(buckets=1 << 12, slots=4,
                                    replicate_reads=False,
                                    stagger_slots=True))
    cfg = HashTableConfig(p=bench["p"], k=bench["p"],
                          queries_per_pe=bench["qpp"], **table)
    # column -> the model regime it measures (stream_throughput.py shapes);
    # scanned = per-step dispatch (full table round trip every step) with
    # the serial commit
    regimes = {
        "mops_scanned": dict(bucket_tiles=1, vectorized_commit=False,
                             fused=False),
        "mops_fused": dict(bucket_tiles=1),
    }
    rows = []
    for r in bench["rows"]:
        for col, kw in regimes.items():
            if col not in r:
                continue
            modeled = stream_modeled_mops(cfg, steps=r["steps"], **kw)
            rows.append(dict(steps=r["steps"], column=col,
                             measured_mops=r[col], modeled_mops=modeled,
                             measured_over_modeled=r[col] / modeled,
                             mix=MIX_DEFAULT.as_tuple()))
    return rows


def bulk_measured_vs_modeled(path: str = "BENCH_bulk.json") -> list:
    """measured-vs-modeled rows for the count-then-place bulk build
    (BENCH_bulk.json x perfmodel.bulk_build_modeled_mops).  The model prices
    the plan's two sort passes + scan passes over the packed record rows at
    VMEM bandwidth plus one port-0 plane round trip; off-TPU the absolute
    gap is host/CPU noise, so the interesting number is the shape across n
    (sort-bound growth) — both are printed."""
    from repro.core.config import HashTableConfig
    from repro.core.perfmodel import bulk_build_modeled_mops
    if not os.path.exists(path):
        return []
    bench = json.load(open(path))
    table = bench.get("table", dict(buckets=1 << 13, slots=4,
                                    replicate_reads=False,
                                    stagger_slots=True))
    cfg = HashTableConfig(p=bench["p"], k=bench["p"], queries_per_pe=8,
                          **table)
    rows = []
    for r in bench["rows"]:
        modeled = bulk_build_modeled_mops(cfg, r["n"])
        rows.append(dict(n=r["n"], keyset=r["keyset"],
                         measured_mops=r["mops_bulk"], modeled_mops=modeled,
                         measured_over_modeled=r["mops_bulk"] / modeled,
                         bulk_over_streamed=r["bulk_over_streamed"],
                         mix=(0.0, 1.0, 0.0, 0.0)))   # construction: all inserts
    return rows


def distributed_measured_vs_modeled(path: str = "BENCH_distributed.json"
                                    ) -> list:
    """measured-vs-modeled rows for the routed distributed stream
    (BENCH_distributed.json x perfmodel.sharded_stream_modeled_mops /
    replicated_read_mops).

    Sharded sweep rows: each router column is re-derived at the benchmark's
    achieved routed shapes — skewproof at the fixed ``D * n_local`` width,
    bounded at the recorded measured width.  The replication_ab section adds
    the 2-D pair: flat 1-D at its bounded width vs the grouped mesh via
    :func:`perfmodel.replicated_read_mops` (measured max per-(step, dest)
    load + the replica-broadcast copy factor from the recorded mix and
    per-shard load fractions).  Off-TPU the absolute gap is interpret/CPU
    noise; the interesting number is agreement on the width-driven RATIOS
    (bounded/skewproof, replicated/flat), which the model attributes
    entirely to routed-width shrink net of broadcast copies."""
    from repro.core.config import HashTableConfig
    from repro.core.perfmodel import (MIX_DEFAULT, as_mix,
                                      replica_copy_factor,
                                      replicated_read_mops,
                                      sharded_stream_modeled_mops)
    if not os.path.exists(path):
        return []
    bench = json.load(open(path))
    rows = []
    steps, nl = bench.get("steps", 16), bench.get("n_local", 8)
    buckets = bench.get("buckets", 1 << 13)
    for r in bench.get("rows", []):
        d = r["shards"]
        cfg = HashTableConfig(p=d, k=d, buckets=buckets, slots=2,
                              queries_per_pe=nl, replicate_reads=False,
                              stagger_slots=True, shards=d)
        br = r["bounded_router"]
        shapes = {
            "mops_sharded_skewproof": dict(routed_width=None),
            "mops_sharded_bounded": dict(routed_width=br["routed_width"],
                                         routed_steps=br["routed_steps"]),
        }
        for col, kw in shapes.items():
            if col not in r:
                continue
            modeled = sharded_stream_modeled_mops(cfg, steps, nl, **kw)
            rows.append(dict(label=f"D{d}__{col}", measured_mops=r[col],
                             modeled_mops=modeled,
                             measured_over_modeled=r[col] / modeled,
                             mix=MIX_DEFAULT.as_tuple()))
    ab = bench.get("replication_ab")
    if ab:
        steps, nl = ab["steps"], ab["n_local"]
        nsq = ab["nsq_fraction"]
        flat = ab["flat"]
        cfg_f = HashTableConfig(p=flat["shards"], k=flat["shards"],
                                buckets=buckets, slots=2, queries_per_pe=nl,
                                replicate_reads=False, stagger_slots=True,
                                shards=flat["shards"], router="bounded")
        m_flat = sharded_stream_modeled_mops(
            cfg_f, steps, nl, routed_width=flat["bounded_router"]
            ["routed_width"], routed_steps=flat["bounded_router"]
            ["routed_steps"], mix=nsq)
        rep = ab["replicated"]
        cfg_r = HashTableConfig(p=ab["n_devices"], k=flat["shards"],
                                buckets=buckets, slots=2, queries_per_pe=nl,
                                replicate_reads=False, stagger_slots=True,
                                shards=rep["shards"], router="bounded",
                                replica_groups=tuple(rep["replica_groups"]))
        frac = [g["shard_load_fraction"] for g in rep["group_occupancy"]]
        max_dest = max(g["max_member_load"] for g in rep["group_occupancy"])
        m_rep = replicated_read_mops(cfg_r, steps, nl,
                                     max_dest_load=max_dest,
                                     routed_steps=rep["bounded_router"]
                                     ["routed_steps"], mix=nsq,
                                     shard_load_fraction=frac)
        ab_mix = as_mix(nsq).as_tuple()
        for label, meas, mod in (("flat", flat["mops"], m_flat),
                                 ("replicated", rep["mops"], m_rep)):
            rows.append(dict(label=f"replication_ab__{label}",
                             measured_mops=meas, modeled_mops=mod,
                             measured_over_modeled=meas / mod, mix=ab_mix))
        rows.append(dict(
            label="replication_ab__ratio",
            measured_mops=ab["replicated_over_flat"],
            modeled_mops=m_rep / m_flat,
            measured_over_modeled=(ab["replicated_over_flat"]
                                   / (m_rep / m_flat)),
            copy_factor=replica_copy_factor(cfg_r, nsq, frac), mix=ab_mix))
    return rows


def serve_measured_vs_modeled(path: str = "BENCH_serve.json") -> list:
    """measured-vs-modeled rows for the continuous-batching serve loop
    (BENCH_serve.json x perfmodel.serve_loop_modeled).

    Each bench mode maps onto the model's knobs: ``oneshot`` is hit_rate=0 /
    single-buffered (a fresh measure+plan every slab), ``cached_single`` is
    the measured plan-cache hit rate with no overlap, ``cached_double``
    additionally hides the host term behind the in-flight slab.  Off-TPU the
    absolute MOPS gap is interpret/CPU noise — the interesting number is the
    measured-vs-modeled agreement on the cached/oneshot and double/single
    RATIOS, which the model attributes entirely to amortized planning and
    overlap."""
    from repro.core.config import HashTableConfig
    from repro.core.perfmodel import MIX_DEFAULT, as_mix, serve_loop_modeled
    if not os.path.exists(path):
        return []
    bench = json.load(open(path))
    table = bench.get("table", dict(buckets=1 << 12, slots=4,
                                    replicate_reads=False,
                                    stagger_slots=True))
    cfg = HashTableConfig(p=bench["p"], k=bench["p"],
                          queries_per_pe=bench["qpp"],
                          shards=bench.get("shards", 1), router="bounded",
                          **table)
    rows = []
    for r in bench["rows"]:
        # the bench may record the served op mix per mode; the model assumes
        # the 50/50 default otherwise — either way the row reports it
        mix = as_mix(tuple(r["op_mix"]) if "op_mix" in r else None)
        m = serve_loop_modeled(cfg, bench["slab_steps"],
                               hit_rate=r.get("hit_rate", 0.0),
                               pad_fraction=r.get("pad_fraction", 0.0),
                               double_buffer=r.get("double_buffer", False),
                               mix=mix)
        rows.append(dict(mode=r["mode"], measured_mops=r["mops"],
                         modeled_mops=m["mops"],
                         measured_p50_ms=r["p50_ms"],
                         modeled_p50_ms=m["p50_seconds"] * 1e3,
                         measured_over_modeled=r["mops"] / m["mops"],
                         mix=mix.as_tuple()))
    return rows


def main() -> None:
    rows = analyze()
    os.makedirs("experiments", exist_ok=True)
    cols = ["arch", "shape", "chips", "compute_s", "memory_s",
            "collective_s", "dominant", "model_flops", "hlo_flops",
            "model_over_hlo", "roofline_frac", "mem_temp_gb", "mem_args_gb"]
    with open("experiments/roofline.csv", "w") as f:
        f.write(",".join(cols) + "\n")
        for r in rows:
            if r.get("status") != "ok":
                continue
            f.write(",".join(str(r.get(c, "")) for c in cols) + "\n")
    for r in rows:
        if r.get("status") != "ok":
            print(f"roofline_{r['arch']}__{r['shape']},0.0,status=FAIL")
            continue
        print(f"roofline_{r['arch']}__{r['shape']},0.0,"
              f"compute_s={r['compute_s']:.3e};memory_s={r['memory_s']:.3e};"
              f"collective_s={r['collective_s']:.3e};dom={r['dominant']};"
              f"frac={r['roofline_frac']:.3f}")
    # assumed search/insert/update/delete mix the model priced each row at
    fmt_mix = lambda r: "mix=" + "/".join(f"{f:.2f}" for f in r["mix"])
    for r in stream_measured_vs_modeled():
        print(f"roofline_stream_T{r['steps']}__{r['column']},0.0,"
              f"measured_MOPS={r['measured_mops']:.3f};"
              f"modeled_MOPS={r['modeled_mops']:.1f};"
              f"measured_over_modeled={r['measured_over_modeled']:.2e};"
              f"{fmt_mix(r)}")
    for r in bulk_measured_vs_modeled():
        print(f"roofline_bulk_{r['keyset']}_n{r['n']},0.0,"
              f"measured_MOPS={r['measured_mops']:.3f};"
              f"modeled_MOPS={r['modeled_mops']:.1f};"
              f"measured_over_modeled={r['measured_over_modeled']:.2e};"
              f"bulk_over_streamed={r['bulk_over_streamed']:.2f};"
              f"{fmt_mix(r)}")
    for r in distributed_measured_vs_modeled():
        extra = (f";copy_factor={r['copy_factor']:.3f}"
                 if "copy_factor" in r else "")
        print(f"roofline_distributed__{r['label']},0.0,"
              f"measured={r['measured_mops']:.3f};"
              f"modeled={r['modeled_mops']:.1f};"
              f"measured_over_modeled={r['measured_over_modeled']:.2e}"
              + extra + f";{fmt_mix(r)}")
    for r in serve_measured_vs_modeled():
        print(f"roofline_serve__{r['mode']},0.0,"
              f"measured_MOPS={r['measured_mops']:.3f};"
              f"modeled_MOPS={r['modeled_mops']:.1f};"
              f"measured_p50_ms={r['measured_p50_ms']:.3f};"
              f"modeled_p50_ms={r['modeled_p50_ms']:.3f};"
              f"measured_over_modeled={r['measured_over_modeled']:.2e};"
              f"{fmt_mix(r)}")


if __name__ == "__main__":
    main()
