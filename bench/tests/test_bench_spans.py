"""The program's host spans read from a reduced trace, and the CG metrics
that read them, on hand-made events."""

import os

import pytest

from bench import harness, spans
from bench.trace import Event, Trace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _trace(with_spans=True):
    # Window 0..100 ns.  Iterations 10-40, 50-80 and 90-120 (clipped to
    # 90-100); reads 30-40, 70-75 and 95-110 (clipped to 95-100), one read
    # before the first iteration (2-5); another thread's spans are not read.
    main = [Event("bench.window", 0, 100), Event("bench.cg_set", 0, 100)]
    if with_spans:
        main += [Event("repro.sync", 2, 5),
                 Event("repro.cg.iter", 10, 40), Event("repro.sync", 30, 40),
                 Event("repro.cg.iter", 50, 80), Event("repro.sync", 70, 75),
                 Event("repro.cg.iter", 90, 120), Event("repro.sync", 95, 110)]
    other = [Event("repro.cg.iter", 0, 100), Event("repro.sync", 0, 100)]
    return Trace([[Event("fusion.1", 0, 100)]], {"main": main, "other": other})


def test_span_count_and_time_in_the_window():
    tr = _trace()
    assert spans.span_count(tr, spans.CG_ITER) == 3
    assert spans.span_count(tr, spans.SYNC) == 4
    assert spans.span_s(tr, spans.CG_ITER) == pytest.approx(70e-9)
    assert spans.span_s(tr, spans.SYNC) == pytest.approx(23e-9)
    assert spans.span_count(tr, "repro.absent") == 0


def test_span_self_time_leaves_out_the_child_spans_inside():
    # 70 ns of iterations less the reads inside them: 10 + 5 + 5 ns.
    assert spans.span_self_s(_trace(), spans.CG_ITER, spans.SYNC) == \
        pytest.approx(50e-9)
    assert spans.span_self_s(_trace(), spans.CG_ITER, "repro.absent") == \
        pytest.approx(70e-9)


def test_span_self_time_counts_overlapping_children_once():
    main = [Event("bench.window", 0, 100), Event("repro.cg.iter", 0, 100),
            Event("repro.sync", 10, 50), Event("repro.sync", 20, 30),
            Event("repro.sync", 40, 60)]
    tr = Trace([[]], {"main": main})
    assert spans.span_self_s(tr, spans.CG_ITER, spans.SYNC) == pytest.approx(50e-9)


def test_span_names_are_the_programs():
    from repro.obs import spans as program
    assert spans.CG_ITER == program.PREFIX + "cg.iter"
    assert spans.SYNC == program.PREFIX + "sync"
    assert {"cg.iter", "sync"} <= set(program.SPANS)


def _read(metric, trace, units):
    reader = harness.load_module(os.path.join(ROOT, "bench", "metrics", metric + ".py"),
                                 "bench.metrics." + metric)
    return reader.read(harness.Context(trace, units, 1.0, 1.0, {}))


@pytest.mark.parametrize("metric,value", [("host_ms.cg", 50e-9 * 1e3 / 3),
                                          ("host_syncs.cg", 4 / 3)])
def test_cg_host_metrics(metric, value):
    assert _read(metric, _trace(), 3) == pytest.approx(value)
    # A program that records no such span (the parent of these metrics).
    assert _read(metric, _trace(with_spans=False), 3) is None
