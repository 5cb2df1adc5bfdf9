"""The trace reduction, on a small trace recorded on the CPU."""
import time

import jax
import jax.numpy as jnp
import pytest

from benchmarks.chip import trace as T


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    d = tmp_path_factory.mktemp("trace")
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((384, 384))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(d), profiler_options=opts)
    for i in range(3):
        with jax.profiler.TraceAnnotation("bench.turn", sid=f"s{i}"):
            f(x).block_until_ready()
        with jax.profiler.TraceAnnotation("bench.idle"):
            time.sleep(0.02)
    jax.profiler.stop_trace()
    return T.load(T.latest_xplane(str(d)))


def test_host_annotations_in_order(recorded):
    names = [h[0] for h in recorded.host]
    assert names == ["bench.turn", "bench.idle"] * 3
    assert [h[3].get("sid") for h in recorded.host[::2]] == ["s0", "s1",
                                                              "s2"]
    idle = [e - s for n, s, e, _ in recorded.host if n == "bench.idle"]
    assert all(0.019e9 < d < 0.2e9 for d in idle)


def test_device_ops_fall_inside_turns(recorded):
    (dev,) = recorded.devices
    assert len(dev.modules) >= 3 and dev.ops
    turns = [(s, e) for n, s, e, _ in recorded.host if n == "bench.turn"]
    for _, s, e in dev.ops:
        assert any(a <= s and e <= b for a, b in turns)


def test_busy_is_union_and_self_times_add_up(recorded):
    (dev,) = recorded.devices
    busy = T.busy(dev)
    assert all(a[1] <= b[0] for a, b in zip(busy, busy[1:]))
    total = T.total(busy)
    assert 0 < total <= sum(e - s for _, s, e in dev.ops)
    assert sum(T.op_seconds(recorded).values()) == pytest.approx(
        total * 1e-9, rel=1e-6)


def test_gaps_are_labelled_by_the_host_span(recorded):
    (dev,) = recorded.devices
    lo = recorded.host[0][1]
    hi = recorded.host[-1][2]
    gaps = T.gaps(T.busy(dev), lo, hi)
    longest = max(gaps, key=lambda g: g[1] - g[0])
    assert T.host_span_at(recorded, sum(longest) / 2) == "bench.idle"


def test_interval_helpers():
    assert T.union([(5, 7), (0, 2), (1, 3), (6, 9)]) == [(0, 3), (5, 9)]
    assert T.gaps([(0, 3), (5, 9)], 0, 12) == [(3, 5), (9, 12)]
    assert T.op_name("%decode_attention.4 = bf16[8] custom-call(x)") == \
        "decode_attention"
    assert T.op_name("%fusion.109 = (f32[8]) fusion(%decode_attention.4)") \
        == "fusion"
    assert T.op_name("while") == "while"
