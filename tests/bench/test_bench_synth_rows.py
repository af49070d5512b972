"""The readers of ``kv_synth_ms_per_dispatch`` and ``kernel_rows_per_dispatch``.

Each reads a value where the program records the ``kv.synth`` span or the
``kernel_rows`` counter, and nothing where it does not, as a program
without them (the one before they were added) does.
"""

import types

import bench_tiny  # noqa: F401  (puts the repo root on sys.path)
import pytest

from bench import harness

SPANS = [("verify", 0.0, 0.2), ("kv.synth", 0.01, 0.013), ("kv.synth", 0.02, 0.021),
         ("nav_queue", 0.0, 0.1), ("verify", 0.3, 0.5), ("kv.synth", 0.31, 0.315)]


def _ctx(spans, start, end):
    return types.SimpleNamespace(program_spans=spans, counters_start=start, counters_end=end,
                                 window_s=1.0, trace=None, traced_dispatches=[])


def test_kv_synth_sums_the_spans_over_the_dispatches():
    ctx = _ctx(SPANS, {"batched_calls": 0}, {"batched_calls": 2})
    value = harness.load_metric("kv_synth_ms_per_dispatch").read(ctx)
    assert value == pytest.approx(1e3 * (0.003 + 0.001 + 0.005) / 2)


def test_kernel_rows_over_the_window_per_dispatch():
    ctx = _ctx([], {"batched_calls": 10, "nav_calls": 50, "kernel_rows": 64},
               {"batched_calls": 14, "nav_calls": 70, "kernel_rows": 64 + 4 * 32})
    assert harness.load_metric("kernel_rows_per_dispatch").read(ctx) == 32.0
    # a padded launch carries at least the sessions it serves
    assert harness.load_metric("batch_occupancy").read(ctx) == 5.0


@pytest.mark.parametrize("name", ["kv_synth_ms_per_dispatch", "kernel_rows_per_dispatch"])
def test_readers_read_nothing_without_the_span_or_counter(name):
    ctx = _ctx([s for s in SPANS if s[0] != "kv.synth"], {"batched_calls": 0, "nav_calls": 0},
               {"batched_calls": 2, "nav_calls": 4})
    assert harness.load_metric(name).read(ctx) is None


@pytest.mark.parametrize("name", ["kv_synth_ms_per_dispatch", "kernel_rows_per_dispatch"])
def test_readers_read_nothing_without_a_dispatch(name):
    ctx = _ctx([("kv.synth", 0.0, 0.1)], {"batched_calls": 3, "kernel_rows": 8},
               {"batched_calls": 3, "kernel_rows": 8})
    assert harness.load_metric(name).read(ctx) is None
