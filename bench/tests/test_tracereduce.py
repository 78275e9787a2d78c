"""The reduction from a trace to busy time, idle gaps and op totals."""

import json
from pathlib import Path

import pytest
import tracereduce as tr


@pytest.fixture
def small():
    raw = json.loads((Path(__file__).parent / "data"
                      / "trace_small.json").read_text())
    return tr.Trace(
        device_ops={p: [tr.Span(*e) for e in ops]
                    for p, ops in raw["device_ops"].items()},
        host_spans=[tr.Span(*e) for e in raw["host_spans"]])


def test_busy_is_the_union_inside_the_window(small):
    got = tr.reduce(small)
    # chip 0: [0, 20) and [30, 40) = 30 ns, the op at 55 lies past the
    # window; chip 1: 50 ns; mean over chips
    assert got["busy_s"] == pytest.approx(40e-9)
    assert got["window_s"] == pytest.approx(50e-9)


def test_idle_gaps_are_labelled_by_the_host_span(small):
    gaps = tr.reduce(small)["idle_gaps"]
    assert gaps == [["bench.dispatch", pytest.approx(10e-9)],
                    ["bench.finalize", pytest.approx(10e-9)]]


def test_op_totals_average_over_chips(small):
    ops = dict(tr.reduce(small)["device_ops"])
    assert ops["encode_search"] == pytest.approx((15 + 50) / 2 * 1e-9)
    assert ops["fusion.1"] == pytest.approx(20 / 2 * 1e-9)


def test_no_window_or_no_device_gives_nothing(small):
    assert tr.reduce(tr.Trace(small.device_ops, [])) is None
    assert tr.reduce(tr.Trace({}, small.host_spans)) is None


def test_load_reads_harness_spans_from_a_profile(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    trace = tr.load(str(tmp_path))
    names = {s.name for s in trace.host_spans}
    assert {tr.WINDOW_SPAN, "bench.dispatch"} <= names
    win = [s for s in trace.host_spans if s.name == tr.WINDOW_SPAN][0]
    inner = [s for s in trace.host_spans if s.name == "bench.dispatch"][0]
    assert win.start <= inner.start <= inner.end <= win.end
