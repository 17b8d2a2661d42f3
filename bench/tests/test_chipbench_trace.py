"""The trace reduction: busy union, op sums, permute exposure, gap labels."""
from __future__ import annotations

import pytest

from chipbench_util import BENCH  # noqa: F401  (puts bench/ on sys.path)
from benchlib import trace as tr

DEV = tr.DEVICE_PREFIX + "0"


def _trace():
    # window 0..10 s.  A while loop 1-4 encloses a convolution 2-3; a
    # permute is in flight 5-8 and its done op runs 7.5-8, half of the
    # flight hidden behind a fusion 5-6.5.  Idle: 0-1 (nothing open), 4-5
    # (host building rows), 6.5-7.5 (dispatching), 8-10 (waiting).
    ops = {DEV: sorted([
        (1.0, 4.0, "%while.1 = (f32[]) while((f32[]) %t), body=%b"),
        (2.0, 3.0, "%convolution.2 = f32[8] convolution(f32[8] %a)"),
        (5.0, 6.5, "%fusion.1 = f32[8] fusion(f32[8] %a), kind=kLoop"),
        (7.5, 8.0, "%collective-permute-done.3 = f32[8] collective-permute-done(%s)"),
        (11.0, 12.0, "%fusion.1 = f32[8] fusion(f32[8] %a), kind=kLoop"),
    ])}
    async_ops = {DEV: [(5.0, 8.0, "%collective-permute-start.3 = (f32[8]) "
                                  "collective-permute-start(f32[8] %x)")]}
    spans = sorted([
        (0.0, 10.0, "bench.window"), (3.9, 5.1, "bench.batch"),
        (6.4, 7.6, "bench.dispatch"), (8.0, 10.0, "bench.wait"),
    ])
    return tr.Trace(ops=ops, spans=spans, window=(0.0, 10.0), async_ops=async_ops)


def test_union_and_busy():
    assert tr.union([(1, 3), (2, 4), (6, 7)]) == [(1, 4), (6, 7)]
    assert tr.busy_s(_trace(), DEV) == pytest.approx(5.0)


def test_op_self_seconds_clipped_to_the_window():
    secs = tr.op_seconds(_trace(), DEV)
    assert secs == pytest.approx({"while.1": 2.0, "convolution.2": 1.0, "fusion.1": 1.5,
                                  "collective-permute-done.3": 0.5})


def test_permute_exposure():
    assert tr.exposed_s(_trace(), DEV, "collective-permute") == pytest.approx(1.5)


def test_idle_gaps_labelled_by_the_open_span():
    assert tr.idle_gaps(_trace(), DEV) == [
        ("other", pytest.approx(1.0)), ("batch", pytest.approx(1.0)),
        ("dispatch", pytest.approx(1.0)), ("wait", pytest.approx(2.0))]


def test_harness_spans_inside_the_window():
    assert tr.span_seconds(_trace(), "batch") == pytest.approx([1.2])


def test_metric_readers_on_a_trace():
    import types

    from benchlib.files import Bench

    bench = Bench(BENCH)
    ctx = types.SimpleNamespace(
        trace=_trace(), devices=[DEV], steps=2, chips=1, tokens_per_s=1000.0,
        peaks={"bf16_flops": 1e12, "hbm_bytes_per_s": 1e9}, flops_per_token=1e8,
        leaf_sizes=[10 ** 6], param_itemsize=2, degree=2)
    read = lambda name: bench.metric(name).read(ctx)
    assert read("device.idle_share") == pytest.approx(50.0)
    assert read("host.batch_ms") == pytest.approx(1200.0)
    assert read("step.mfu") == pytest.approx(10.0)
    assert read("gossip.permute_ms") == pytest.approx(1500.0)
    assert read("gossip.exposed_ms") == pytest.approx(750.0)
    # no kernel events in this trace: the reader finds nothing to read
    assert read("kernel.gossip_update.roofline") is None
