"""The comparison that decides ``correct``: served rounds against the reference.

Three numbers, each with its limit from the configuration's file:

* ``delivery_mismatches`` (exact, limit 0): rounds where what the edge sent
  or received differs from what the verify backend was given or returned,
  where a result breaks greedy NAV's own rules (``n_accepted`` past the
  drafts, a correction equal to the draft it rejected, an id outside the
  vocabulary), a dispatched round that no edge result matches, or a round
  whose result never came.
* ``served_gap_max``: over every served token (accepted drafts and the
  correction), how far the reference's logit of that token lies below the
  reference's best logit on its row.  Greedy serving reads 0.
* ``logp_rms_diff``: the root mean square of the gaps between the draft
  log-probs the verify backend returned and the reference's, over
  in-vocabulary drafts.  The widest such gap, ``logp_max_abs_diff``, is
  read too and printed, but not compared: it grows with the number of
  drafts a run compares, where the root mean square holds steady.

Every round that got a result in the run is compared.
"""

from __future__ import annotations

import importlib.util
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Sequence, Tuple

import numpy as np

REF_DIR = Path(__file__).resolve().parent / "references"


def load_reference(name: str, directory: Path = REF_DIR):
    """Import ``<directory>/<name>.py``, a configuration's plain reference."""
    path = Path(directory) / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_reference_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def match_dispatches(rounds: Sequence[Dict], dispatched: Sequence[Dict]) -> Tuple[List, int]:
    """Pair each edge round with the backend request that served it.

    Both sides are in order within a session (one round in flight per
    session), so the k-th result a session received is the k-th request the
    backend saw for it.  Returns ``[(round, request or None)]`` for rounds
    with a result, and the number of mismatches found on the way.
    """
    by_session = defaultdict(list)
    for d in dispatched:
        by_session[d["session"]].append(d)
    mismatches = 0
    pairs = []
    seen = defaultdict(int)
    for r in sorted(rounds, key=lambda r: (r["session"], r["round"])):
        if r.get("t_res") is None:
            mismatches += 1  # an answer that never came
            continue
        i = seen[r["session"]]
        seen[r["session"]] += 1
        reqs = by_session.get(r["session"], [])
        req = reqs[i] if i < len(reqs) else None
        if req is None:
            mismatches += 1
        pairs.append((r, req))
    for s, reqs in by_session.items():  # dispatched, but no edge result matches
        mismatches += max(len(reqs) - seen.get(s, 0), 0)
    return pairs, mismatches


def _delivery_ok(r: Dict, req: Dict, vocab: int) -> bool:
    K, n, corr = len(r["tokens"]), r["n_accepted"], r["correction"]
    if req is None:
        return False
    if list(req["tokens"]) != list(r["tokens"]):
        return False
    if (req["n_accepted"], req["correction"]) != (n, corr) or len(req["logp"]) != K:
        return False
    if not (0 <= n <= K and 0 <= corr < vocab):
        return False
    return not (n < K and r["tokens"][n] == corr)


def readings(model: Dict, seed: int, rounds, dispatched, *, prefix: int, reference, w=None) -> Dict:
    """The three numbers for one run (see the module docstring)."""
    V = model["vocab_size"]
    pairs, mismatches = match_dispatches(rounds, dispatched)
    good = []
    for r, req in pairs:
        if _delivery_ok(r, req, V):
            good.append((r, req))
        else:
            mismatches += 1
    K1 = reference.K1_MAX
    ask = np.full((len(good), K1, 2), -1, np.int64)
    for j, (r, _) in enumerate(good):
        toks = r["tokens"]
        for i, t in enumerate(toks):
            ask[j, i, 0] = t if t < V else -1
        ask[j, r["n_accepted"], 1] = r["correction"]
    st = reference.row_stats(model, seed, [r for r, _ in good], ask, prefix=prefix, w=w)
    gap, diffs, n_served = 0.0, [], 0
    for j, (r, req) in enumerate(good):
        n = r["n_accepted"]
        for i in range(n):  # accepted drafts are served tokens too
            gap = max(gap, float(st["max"][j, i] - st["picked"][j, i, 0]))
        gap = max(gap, float(st["max"][j, n] - st["picked"][j, n, 1]))
        n_served += n + 1
        for i, t in enumerate(r["tokens"]):
            if t < V:
                ref = float(st["picked"][j, i, 0] - st["lse"][j, i])
                diffs.append(abs(float(req["logp"][i]) - ref))
    return dict(_logp_numbers(diffs, gap), delivery_mismatches=mismatches,
                rounds_compared=len(good), served_tokens=n_served)


def _logp_numbers(diffs: List[float], gap: float) -> Dict:
    """The served gap and the log-prob gaps' widest and root-mean-square values."""
    d = np.asarray(diffs, np.float64)
    out = {"served_gap_max": gap,
           "logp_max_abs_diff": float(d.max()) if d.size else 0.0,
           "logp_rms_diff": float(np.sqrt(np.mean(d * d))) if d.size else 0.0,
           "logp_compared": int(d.size)}
    if not all(np.isfinite(out[k]) for k in ("served_gap_max", "logp_max_abs_diff", "logp_rms_diff")):
        out.update(served_gap_max=float("inf"), logp_max_abs_diff=float("inf"), logp_rms_diff=float("inf"))
    return out


def control_readings(model: Dict, seed: int, rounds, *, prefix: int, precision: str, reference, w=None) -> Dict:
    """The reference at a lower ``precision`` put in the program's place.

    On the same rounds (prompts and drafted tokens): the gap, under the
    float32 reference, of the token the lower precision puts first on each
    row a result is served from, and the widest gap between its draft
    log-probs and the reference's.
    """
    V = model["vocab_size"]
    rounds = [r for r in rounds if r.get("t_res") is not None]
    K1 = reference.K1_MAX
    ask = np.full((len(rounds), K1, 2), -1, np.int64)
    for j, r in enumerate(rounds):
        for i, t in enumerate(r["tokens"]):
            ask[j, i, 0] = t if t < V else -1
    low = reference.row_stats(model, seed, rounds, ask, prefix=prefix, precision=precision, w=w)
    for j, r in enumerate(rounds):
        ask[j, :, 1] = low["argmax"][j]
    ref = reference.row_stats(model, seed, rounds, ask, prefix=prefix, w=w)
    gap, diffs = 0.0, []
    for j, r in enumerate(rounds):
        K = len(r["tokens"])
        for i in range(K + 1):
            gap = max(gap, float(ref["max"][j, i] - ref["picked"][j, i, 1]))
        for i, t in enumerate(r["tokens"]):
            if t < V:
                lo = float(low["picked"][j, i, 0] - low["lse"][j, i])
                hi = float(ref["picked"][j, i, 0] - ref["lse"][j, i])
                diffs.append(abs(lo - hi))
    return dict(_logp_numbers(diffs, gap), delivery_mismatches=0, rounds_compared=len(rounds))


def judge(values: Dict, limits: Dict) -> Tuple[bool, Dict]:
    """``correct`` and the ``{number: {value, limit}}`` table, one row per limit."""
    table = {k: {"value": values[k], "limit": limits[k]} for k in limits}
    ok = all(np.isfinite(float(t["value"])) and t["value"] <= t["limit"] for t in table.values())
    return bool(ok), table
