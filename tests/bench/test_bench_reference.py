"""The plain reference against the launcher's backend, and its control.

The reference (``bench/references/synthetic_target.py``) imports nothing of
the program.  Here it must agree with what ``launch/serve.py``'s fused
backend serves, run through the Pallas kernel in interpret mode on the CPU,
at a tiny width; and the reference computed a step below float32 must come
out not correct by the limits of the configuration it guards.
"""

import json

import bench_tiny
import numpy as np
import pytest

from bench import compare
from bench.harness import _load_launcher
from bench.references import synthetic_target as st

PREFIX = 32


def _served(monkeypatch, seed, rounds_per_session=3, sessions=(3, 8, 21)):
    """Serve a few sessions' rounds through the launcher's backend directly."""
    bench_tiny.tiny_arch(monkeypatch)
    serve = _load_launcher()
    args = serve.build_parser().parse_args(
        ["--listen", "127.0.0.1:0", "--backend", "spec", "--impl", "interpret",
         "--arch", "tiny-bench", "--seed", str(seed)])
    backend, kw = serve._make_backend(args)
    pool = kw["kv_pool"]
    pool.create(-1)
    pool.append(-1, PREFIX)
    backend.ensure_kv(-1)
    rng = np.random.default_rng(seed)
    committed = {s: 0 for s in sessions}
    for s in sessions:
        pool.fork(-1, s)
    rounds, dispatched = [], []
    for rnd in range(rounds_per_session):
        reqs = []
        for s in sessions:
            k = int(rng.integers(1, 5))
            toks = [int(t) for t in rng.integers(0, 65536, size=k)]
            pool.append(s, PREFIX + committed[s] + k + 1 - pool.length(s))
            reqs.append((s, toks, [0.5] * k))
        out = backend.fused_verify(reqs)
        for (s, toks, _), (n, c, lp) in zip(reqs, out):
            rounds.append({"session": s, "round": rnd + 1, "pos": committed[s], "tokens": toks,
                           "n_accepted": int(n), "correction": int(c), "t_res": 1.0})
            dispatched.append({"session": s, "tokens": toks, "n_accepted": int(n),
                               "correction": int(c), "logp": [float(x) for x in lp]})
            committed[s] += int(n) + 1
            pool.rollback(s, PREFIX + committed[s])
    return rounds, dispatched


def _model():
    return json.loads((bench_tiny.DATA / "configs" / "tiny.json").read_text())


@pytest.mark.parametrize("seed", [5, 2**31 - 3])
def test_reference_agrees_with_the_served_kernel(monkeypatch, seed):
    rounds, dispatched = _served(monkeypatch, seed)
    v = compare.readings(_model(), seed, rounds, dispatched, prefix=PREFIX, reference=st)
    assert v["rounds_compared"] == len(rounds) == 9
    assert v["delivery_mismatches"] == 0
    assert v["served_gap_max"] == 0.0
    assert v["logp_max_abs_diff"] < 1e-4
    assert v["logp_compared"] > 0


def test_reference_sees_a_wrong_context_length(monkeypatch):
    """Off-by-one in the positions a row attends is caught (not a tautology)."""
    rounds, dispatched = _served(monkeypatch, 9)
    shifted = [dict(r, pos=r["pos"] + 1) for r in rounds]
    v = compare.readings(_model(), 9, shifted, dispatched, prefix=PREFIX, reference=st)
    assert v["logp_max_abs_diff"] > 1e-3


@pytest.mark.parametrize("precision", ["high", "bf16"])
def test_control_fails_the_limit(precision):
    """The reference a step below float32 in the program's place fails ``correct``.

    At granite-3-2b's widths, on a few rounds at the cell's contexts, the
    reference computed with three bfloat16 passes (``high``, the step below
    the configuration's float32 at ``highest``) or one (``bf16``) is judged
    by the configuration's own limits, as a run of the program is.
    """
    model = json.loads((bench_tiny.REPO / "bench" / "configs" / "granite-3-2b.json").read_text())
    rng = np.random.default_rng(1)
    rounds = [{"session": int(s), "pos": int(p), "t_res": 1.0,
               "tokens": [int(t) for t in rng.integers(0, model["vocab_size"], size=k)]}
              for s, p, k in zip(rng.integers(1, 500, 24), rng.integers(0, 190, 24), rng.integers(1, 5, 24))]
    c = compare.control_readings(model, 7, rounds, prefix=512, precision=precision, reference=st)
    correct, table = compare.judge(c, model["correct_limits"])
    assert not correct, table
