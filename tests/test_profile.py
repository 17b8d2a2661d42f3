"""The names the program gives its work in a profiler trace
(``repro.telemetry.profile``)."""
import jax
import jax.numpy as jnp
import pytest

from repro.telemetry import profile


def test_unknown_names_are_refused():
    with pytest.raises(ValueError, match="SPANS"):
        profile.span("step.nothing")
    with pytest.raises(ValueError, match="SCOPES"):
        profile.scope("nothing")
    with pytest.raises(ValueError, match="COUNTERS"):
        profile.count("attention.path.nothing")


def test_trace_time_counters_add_up():
    before = profile.traced()
    assert set(before) == set(profile.COUNTERS)
    profile.count("attention.path.chunked", 3)
    profile.count("attention.path.chunked")
    after = profile.traced()
    assert after["attention.path.chunked"] - before["attention.path.chunked"] == 4
    assert all(after[n] == before[n] for n in profile.COUNTERS
               if n != "attention.path.chunked")


def test_spans_are_named_under_the_prefix():
    assert all(not s.startswith(profile.SPAN_PREFIX) for s in profile.SPANS)
    assert profile.TRAIN_STEP == profile.SPAN_PREFIX + profile.SPANS[0]
    with profile.step_span(3), profile.span("step.dispatch"):
        pass


@pytest.mark.parametrize("fused", [False, True], ids=["interpreter", "kernel"])
def test_bucket_step_names_its_phases(fused):
    """A bucket executable's update, mixing and folded probe carry the
    scopes the device-time reduction reads."""
    from repro.core.buckets import build_bucket_step
    from repro.core.graphs import Ring
    from repro.core.schedule import compile_graph

    program = compile_graph(Ring(4))
    fn = build_bucket_step(
        program, hyper={"kind": "sgd", "momentum": 0.9}, has_momentum=True,
        kernel_split=(program, ()) if fused else None,
    )
    x = jnp.ones((4, 1024), jnp.float32)
    text = jax.jit(fn).lower(x, x, x, 0.1, jnp.zeros((4,))).compile().as_text()
    want = ("fused_update/", "probe/") if fused else ("optimizer/", "gossip/", "probe/")
    for scope in want:
        assert scope in text, scope


def test_consensus_probe_is_named():
    from repro.core.consensus import consensus_distance_jit

    text = consensus_distance_jit.lower({"w": jnp.ones((4, 8))}).compile().as_text()
    assert "probe/" in text
