"""``kernel.wkv.roofline`` on synthetic traces: nothing to read without the
WKV kernels' events, and at a tiny size its FLOP and byte counts are those
counted by hand."""
from __future__ import annotations

import types

import pytest

import chipbench_util
from chipbench_util import tiny_bench
from benchlib import trace as tr
from benchlib.files import Bench

CELL = "rwkv6-1chip-s4096-b1"
chipbench_util.TINY_SIZES.setdefault("rwkv6", chipbench_util.TINY_SIZES["ssm"])
DEV = tr.DEVICE_PREFIX + "0"
NAME = "kernel.wkv.roofline"

# The tiny cell: hidden 256 in 4 heads of 64, one row of 96 tokens in
# bfloat16.  The kernel's chunk is 64 and its sub-chunk 32, so the row pads
# to 128: 2 chunks of 2 sub-chunks, 8 (batch, head, chunk) steps a call.
# MACs a step, forward: cumulative sums 2 x 64·64·64 = 524,288; readout and
# state update 2 x 64·64·64 = 524,288; in-sub-chunk scores times v
# 2 x 32·32·64 = 131,072; cross-sub-chunk scores and their product with v
# 2 x 32·32·64 = 131,072: 1,310,720.  Backward: cumulative sums 4 x 262,144;
# state products 4 x 262,144; five cross-sub-chunk products 5 x 65,536:
# 2,424,832.
FLOPS = {"wkv_fwd": 2 * 8 * 1_310_720, "wkv_fwd_states": 2 * 8 * 1_310_720,
         "wkv_bwd": 2 * 8 * 2_424_832}
# Bytes: a (1, 128, 4, 64) bfloat16 operand is 65,536, the float32 state
# (1, 4, 64, 64) 65,536, the two chunks' states 131,072, the float32 bonus
# (4, 64) 1,024.  Forward: r, k, v, log decay and bonus in, o out, first
# state in and last out; the forward that keeps the chunks' states also
# writes them.  Backward: r, k, v, log decay, do in and four gradients out,
# the chunks' states in, the last state's gradient in and the first's out,
# the bonus in and its gradient out.
BYTES = {"wkv_fwd": 5 * 65_536 + 1_024 + 2 * 65_536,
         "wkv_fwd_states": 5 * 65_536 + 1_024 + 2 * 65_536 + 131_072,
         "wkv_bwd": 9 * 65_536 + 131_072 + 2 * 65_536 + 2 * 1_024}


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    root = tiny_bench(tmp_path_factory.mktemp("roof"), CELL, seq=96, rows=1,
                      dtype="bfloat16")
    bench = Bench(root)
    cell = bench.workload("tiny")
    return bench, cell, bench.config(cell["config"])


def _ctx(tiny, ops, peaks):
    bench, cell, config = tiny
    end = max((e for _, e, _ in ops), default=1.0)
    trace = tr.Trace(ops={DEV: ops}, spans=[(0.0, end, "bench.window")],
                     window=(0.0, end))
    return types.SimpleNamespace(trace=trace, devices=[DEV], steps=1, chips=1,
                                 cell=cell, config=config, peaks=peaks)


def test_counts_equal_the_hand_count(tiny):
    bench, cell, config = tiny
    metric = bench.metric(NAME)
    s = metric.shapes(config, bench.traffic(cell["traffic"]))
    assert s == {"b": 1, "l": 128, "h": 4, "n": 64, "c": 64, "sc": 32, "itemsize": 2}
    for kind in FLOPS:
        assert metric.flops(kind, s) == FLOPS[kind], kind
        assert metric.bytes_moved(kind, s) == BYTES[kind], kind


@pytest.mark.parametrize("flop_peak, bound", [(1e12, "bytes"), (1e9, "flops")])
def test_reads_the_least_time_over_the_kernels_events(tiny, flop_peak, bound):
    """One event of each kernel (1, 1 and 2 ms) among other ops; the least
    time of each is its bytes at 1 GB/s or, with a FLOP peak of 1 GFLOP/s,
    its FLOPs."""
    ops = [
        (0.000, 0.001, "%wkv_fwd.1 = (bf16[1,4,128,64]) custom-call(), "
                       "custom_call_target=\"tpu_custom_call\""),
        (0.001, 0.002, "%fusion.7 = f32[8] fusion(f32[8] %a), kind=kLoop"),
        (0.002, 0.003, "%wkv_fwd_states.2 = (bf16[1,4,128,64]) custom-call()"),
        (0.003, 0.005, "%wkv_bwd.3 = (bf16[1,4,128,64]) custom-call()"),
    ]
    table = FLOPS if bound == "flops" else BYTES
    rate = flop_peak if bound == "flops" else 1e9
    least = sum(table[k] for k in ("wkv_fwd", "wkv_fwd_states", "wkv_bwd")) / rate
    got = tiny[0].metric(NAME).read(
        _ctx(tiny, ops, {"bf16_flops": flop_peak, "hbm_bytes_per_s": 1e9}))
    assert got == pytest.approx(100.0 * least / 0.004)


def test_reads_nothing_without_the_kernels_events(tiny):
    """A program without the kernel (the XLA chunk scan, or the parent of
    the kernel) gives no reading."""
    ops = [(0.0, 0.001, "%fusion.964 = f32[8] fusion(f32[8] %a), kind=kLoop")]
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    assert tiny[0].metric(NAME).read(_ctx(tiny, ops, peaks)) is None
