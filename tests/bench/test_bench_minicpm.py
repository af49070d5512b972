"""minicpm-2b's configuration against the plain reference, and its control.

minicpm-2b is multi-head (as many KV heads as query heads), so the served
path repeats no KV head, and its vocabulary (122,753) is no multiple of the
kernel's ``block_v``.  A served round through the launcher's backend with
both properties, at a tiny width, must agree with the reference
(``bench/references/synthetic_target.py``) in its logit statistics and
log-probs; and the reference computed a step below float32 at minicpm-2b's
own widths must come out not correct by the limits in
``bench/configs/minicpm-2b.json``.
"""

import json

import bench_tiny
import jax.numpy as jnp
import numpy as np
import pytest

from bench import compare
from bench.harness import _load_launcher
from bench.references import synthetic_target as st

PREFIX = 32
MODEL = json.loads((bench_tiny.REPO / "bench" / "configs" / "minicpm-2b.json").read_text())
TINY_MHA = dict(MODEL, name="tiny-mha", arch="tiny-mha", hidden_size=64, num_attention_heads=4,
                num_key_value_heads=4, head_dim=16, vocab_size=1000, kv_pool_layers=2)


def _mha_arch(monkeypatch) -> None:
    import repro.configs
    from repro.models.config import ModelConfig

    m = TINY_MHA
    tiny = ModelConfig(
        name=m["arch"], family="dense", n_layers=m["kv_pool_layers"], d_model=m["hidden_size"],
        n_heads=m["num_attention_heads"], n_kv_heads=m["num_key_value_heads"], d_ff=128,
        vocab_size=m["vocab_size"], head_dim=m["head_dim"],
    )
    real = repro.configs.get_config
    monkeypatch.setattr(repro.configs, "get_config",
                        lambda arch, reduced=False: tiny if arch == m["arch"] else real(arch, reduced))


def _served_mha(monkeypatch, seed, rounds_per_session=3, sessions=(2, 5, 17)):
    """Serve a few sessions' rounds through the launcher's fused backend, with
    ``jnp.repeat`` (the GQA head repeat) refused while it runs."""
    _mha_arch(monkeypatch)
    serve = _load_launcher()
    args = serve.build_parser().parse_args(
        ["--listen", "127.0.0.1:0", "--backend", "spec", "--impl", "interpret",
         "--arch", TINY_MHA["arch"], "--seed", str(seed)])
    backend, kw = serve._make_backend(args)
    pool = kw["kv_pool"]
    assert pool.n_kv_heads == TINY_MHA["num_attention_heads"]

    def no_repeat(*a, **k):
        raise AssertionError("an MHA launch repeated KV heads")

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
            k = int(rng.integers(1, 9))
            toks = [int(t) for t in rng.integers(0, TINY_MHA["vocab_size"], size=k)]
            pool.append(s, PREFIX + committed[s] + k + 1 - pool.length(s))
            reqs.append((s, toks, [0.5] * k))
        with monkeypatch.context() as m:
            m.setattr(jnp, "repeat", no_repeat)
            out = backend.fused_verify(reqs)
        for (s, toks, _), (n, c, lp) in zip(reqs, out):
            rounds.append({"session": s, "round": rnd + 1, "pos": committed[s], "tokens": toks,
                           "n_accepted": int(n), "correction": int(c), "t_res": 1.0})
            dispatched.append({"session": s, "tokens": toks, "n_accepted": int(n),
                               "correction": int(c), "logp": [float(x) for x in lp]})
            committed[s] += int(n) + 1
            pool.rollback(s, PREFIX + committed[s])
    return rounds, dispatched


@pytest.mark.parametrize("seed", [3, 2**31 + 11])
def test_served_mha_round_agrees_with_the_reference(monkeypatch, seed):
    """No KV head repeat, vocabulary padded from 1000 to 1024 lanes: every
    served token is the reference's best on its row (gap 0) and the draft
    log-probs match the reference's within minicpm-2b's limit."""
    rounds, dispatched = _served_mha(monkeypatch, seed)
    v = compare.readings(TINY_MHA, seed, rounds, dispatched, prefix=PREFIX, reference=st)
    assert v["rounds_compared"] == len(rounds) == 9
    assert v["delivery_mismatches"] == 0
    assert v["served_gap_max"] == 0.0
    assert v["logp_compared"] > 0
    assert v["logp_rms_diff"] <= MODEL["correct_limits"]["logp_rms_diff"]
    correct, table = compare.judge(v, MODEL["correct_limits"])
    assert correct, table


@pytest.mark.parametrize("precision", ["high", "bf16"])
def test_control_fails_the_minicpm_limit(precision):
    """The reference a step below float32 in the program's place fails ``correct``.

    At minicpm-2b's widths (36 MHA heads of 64, vocab 122,753), on a few
    rounds at the cell's contexts, the reference computed with three
    bfloat16 passes (``high``) or one (``bf16``) is judged by
    ``bench/configs/minicpm-2b.json``'s own limits, as a run of the program is.
    """
    rng = np.random.default_rng(2)
    rounds = [{"session": int(s), "pos": int(p), "t_res": 1.0,
               "tokens": [int(t) for t in rng.integers(0, MODEL["vocab_size"], size=k)]}
              for s, p, k in zip(rng.integers(1, 500, 24), rng.integers(0, 190, 24), rng.integers(1, 9, 24))]
    c = compare.control_readings(MODEL, 7, rounds, prefix=512, precision=precision, reference=st)
    correct, table = compare.judge(c, MODEL["correct_limits"])
    assert not correct, table
