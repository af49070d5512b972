"""A whole run at CPU size, sound and with the timed path broken underneath.

``bench.harness.run`` is driven as ``bench/run.py`` drives it, minus the
look for a chip: launcher backend (``impl='ref'`` on the CPU), verifier,
edge process, window, comparison.  A sound run comes out correct; each fault
the cell can have comes out not correct:

* ``pool_unchanged``: the pool's writes return with the pages unchanged;
* ``half_batch``: the backend verifies only the first half of each batch and
  hands the rest the first session's result;
* ``token_altered``: the correction token is altered where it is produced.

One chip, so the fault of an exchange between chips does not apply.
"""

import time

import bench_tiny
import pytest

from bench import harness


@pytest.mark.parametrize("fault", [None, *harness.FAULTS])
def test_run_is_correct_only_when_sound(tmp_path, monkeypatch, fault):
    bench_tiny.tiny_arch(monkeypatch)
    root = bench_tiny.tiny_root(tmp_path)
    res = harness.run(bench_tiny.CELL, 2**31 + 11, 2.0, False, t_start=time.monotonic(),
                      root=root, impl="ref", fault=fault)
    assert res["extra"]["rounds_compared"] > 10
    if fault is None:
        assert res["extra"]["compiles_in_window"] == 0
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"committed_tok_s", "nav_rtt_p50_ms", "nav_rtt_p95_ms", "setup_s"}
    assert res["correct"] is (fault is None), res["compared"]
    assert list(res["compared"]) == ["delivery_mismatches", "served_gap_max", "logp_rms_diff"]
