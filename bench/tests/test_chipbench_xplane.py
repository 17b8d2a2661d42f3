"""The trace reduction on small traces recorded on a TPU v5e: a jitted
matmul loop inside the harness's spans (``bench.window``, ``bench.batch``,
``bench.dispatch``, ``bench.wait``)."""
from __future__ import annotations

from pathlib import Path

import pytest

from chipbench_util import BENCH
from benchlib import trace as tr

DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def one_chip():
    return tr.load(DATA / "small_1chip.xplane.pb")


def test_spans_and_device_ops_are_read(one_chip):
    names = {n for _, _, n in one_chip.spans}
    assert {"bench.window", "bench.batch", "bench.dispatch", "bench.wait"} <= names
    assert list(one_chip.ops) == [tr.DEVICE_PREFIX + "0"]
    assert one_chip.window_s > 0


def test_busy_time_lies_inside_the_window(one_chip):
    dev = tr.DEVICE_PREFIX + "0"
    busy = tr.busy_s(one_chip, dev)
    assert 0 < busy < one_chip.window_s
    secs = tr.op_seconds(one_chip, dev)
    assert sum(secs.values()) == pytest.approx(busy, rel=1e-6)
    assert all(" = " not in name for name in secs)


def test_idle_gaps_add_up_and_carry_span_labels(one_chip):
    dev = tr.DEVICE_PREFIX + "0"
    gaps = tr.idle_gaps(one_chip, dev)
    assert sum(s for _, s in gaps) == pytest.approx(
        one_chip.window_s - tr.busy_s(one_chip, dev), rel=1e-6)
    assert {label for label, _ in gaps} <= {"batch", "dispatch", "wait", "other"}
    assert len(tr.span_seconds(one_chip, "batch")) == 3


@pytest.fixture(scope="module")
def four_chips():
    """The same loop on four chips, each step ending in a ring ppermute."""
    return tr.load(DATA / "small_4chip.xplane.pb")


def test_four_chips_each_have_their_ops(four_chips):
    assert sorted(four_chips.ops) == [tr.DEVICE_PREFIX + str(i) for i in range(4)]
    for d in four_chips.ops:
        assert 0 < tr.busy_s(four_chips, d) < four_chips.window_s


def test_permutes_in_flight_are_recorded_on_the_first_chip(four_chips):
    devices = sorted(four_chips.ops)
    assert tr.chips_in_flight(four_chips, devices, "collective-permute") == devices[:1]
    d = devices[0]
    flight = tr.length(tr.union(tr.matching(four_chips, d, "collective-permute")))
    exposed = tr.exposed_s(four_chips, d, "collective-permute")
    assert 0 < exposed <= flight


def test_permute_metrics_read_the_four_chip_trace(four_chips):
    import types

    from benchlib.files import Bench

    bench = Bench(BENCH)
    ctx = types.SimpleNamespace(trace=four_chips, devices=sorted(four_chips.ops), steps=3)
    permute = bench.metric("gossip.permute_ms").read(ctx)
    exposed = bench.metric("gossip.exposed_ms").read(ctx)
    assert 0 < exposed <= permute
