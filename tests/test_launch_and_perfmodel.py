"""Launch-layer units: the 40-cell matrix, input specs, perf models."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs import ARCHS, get_config
from repro.core import HashTableConfig
from repro.core.perfmodel import (FPGA_U250, fpga_latency_ns,
                                  fpga_throughput_mops, routed_exchange_bytes,
                                  routed_width_lanes,
                                  sharded_stream_modeled_mops,
                                  stream_commit_seconds, stream_modeled_mops,
                                  table_step_bytes, tpu_modeled_mops)
from repro.launch.shapes import LONG_OK, SHAPES, cells, input_specs


def test_cell_matrix_is_40_with_7_skips():
    all_cells = list(cells())
    assert len(all_cells) == 40
    skipped = [c for c in all_cells if c[2]]
    assert len(skipped) == 7
    assert all(s == "long_500k" for _, s, _ in skipped)
    assert {a for a, _, sk in all_cells if sk} == \
        set(a for a in ARCHS if a not in LONG_OK)


@pytest.mark.parametrize("arch", ["gemma3_1b", "pixtral_12b", "whisper_tiny"])
def test_input_specs_shapes(arch):
    cfg = get_config(arch)
    sds, logical = input_specs(cfg, "train_4k")
    B, S = SHAPES["train_4k"]["batch"], SHAPES["train_4k"]["seq"]
    if cfg.frontend == "vision_patches":
        assert sds["tokens"].shape == (B, S - cfg.num_patches)
        assert sds["patches"].shape == (B, cfg.num_patches, cfg.d_model)
    else:
        assert sds["tokens"].shape == (B, S)
    if cfg.frontend == "audio_frames":
        assert sds["frames"].shape == (B, cfg.encoder_seq, cfg.d_model)
    tok, pos = input_specs(cfg, "decode_32k")[0]
    assert tok.shape == (SHAPES["decode_32k"]["batch"], 1)
    assert pos.shape == ()


def test_fpga_model_calibration():
    # paper: 14 ns search / 54 ns insert at 370 MHz with 16 PEs
    assert fpga_latency_ns("search", 16) == pytest.approx(13.5, abs=1.0)
    assert fpga_latency_ns("insert", 16) == pytest.approx(54.0, abs=1.0)
    # paper: 5926 MOPS at 16 PEs 370 MHz
    assert fpga_throughput_mops(16, 370.0) == pytest.approx(5920, rel=0.01)


def test_tpu_model_monotonic_in_k():
    """Bandwidth-bound MOPS must fall as k (gathered stores) grows — the
    TPU-native reading of the NSQ-ratio optimization."""
    mops = [tpu_modeled_mops(HashTableConfig(
        p=16, k=k, buckets=1 << 14, slots=4, replicate_reads=False))
        for k in (1, 2, 4, 8, 16)]
    assert all(a > b for a, b in zip(mops, mops[1:]))


def test_step_bytes_scales():
    c1 = HashTableConfig(p=8, k=2, buckets=256, slots=2)
    c2 = HashTableConfig(p=8, k=8, buckets=256, slots=2)
    assert table_step_bytes(c2) > table_step_bytes(c1)


def test_stream_model_regime_ordering():
    """The stream model's terms order the regimes the way the kernels do
    (DESIGN.md §3.1): vectorized commit beats serial, fused beats the
    scanned per-step dispatch, and the blocked sweep amortizes with T."""
    cfg = HashTableConfig(p=8, k=8, buckets=1 << 12, slots=4,
                          replicate_reads=False, queries_per_pe=8)
    assert stream_commit_seconds(cfg, vectorized=True) < \
        stream_commit_seconds(cfg, vectorized=False)
    assert stream_modeled_mops(cfg, steps=32) > \
        stream_modeled_mops(cfg, steps=32, vectorized_commit=False)
    assert stream_modeled_mops(cfg, steps=32) > \
        stream_modeled_mops(cfg, steps=32, vectorized_commit=False,
                            fused=False)
    assert stream_modeled_mops(cfg, steps=32, bucket_tiles=8) > \
        stream_modeled_mops(cfg, steps=2, bucket_tiles=8)


def test_routed_width_term_orders_routers():
    """The routed-width term (DESIGN.md §2.2): the bounded router's width
    follows the measured load (tile-rounded, slack-capped, never wider than
    skew-proof), shrinks the exchange payload proportionally, and a narrower
    width models as higher sharded throughput."""
    d, nl = 8, 8
    cfg = HashTableConfig(p=d, k=d, buckets=1 << 12, slots=2, shards=d,
                          queries_per_pe=nl, replicate_reads=False,
                          router="bounded", routed_lane_tile=8)
    skew = HashTableConfig(p=d, k=d, buckets=1 << 12, slots=2, shards=d,
                           queries_per_pe=nl, replicate_reads=False)
    assert routed_width_lanes(skew, nl) == d * nl
    assert routed_width_lanes(cfg, nl, max_owner_load=13) == 16
    assert routed_width_lanes(cfg, nl, max_owner_load=d * nl + 5) == d * nl
    capped = HashTableConfig(p=d, k=d, buckets=1 << 12, slots=2, shards=d,
                             queries_per_pe=nl, replicate_reads=False,
                             router="bounded", routed_slack=12)
    assert routed_width_lanes(capped, nl, max_owner_load=40) == 12
    # skew-proof slots: bucket+op+key+val out, found+ok+val back (7 words);
    # bounded slots add the FIFO step-tag word (8) but ride 4x fewer lanes
    assert routed_exchange_bytes(cfg, 16, nl) == 4 * 16 * 64 * 7
    assert routed_exchange_bytes(cfg, 16, nl, routed_width=16) == \
        4 * 16 * 16 * 8
    assert sharded_stream_modeled_mops(cfg, 16, nl, routed_width=16) > \
        sharded_stream_modeled_mops(cfg, 16, nl)    # and models as throughput
