"""The one traffic generator: a mix file of parameters plus a seed -> a schedule.

A mix (``bench/traffic/<name>.json``) fixes the loop, the session count,
the distribution of tokens each session commits, the shared prefix, the
edge's draft and link settings and the verifier's batching.  The seed only
permutes: every seed gets the same multiset of session lengths, taken at
fixed quantiles of the mix's distribution, in a different order.  So two
seeds do the same work, and a run's spread is the system's, not the
generator's.
"""

from __future__ import annotations

import json
import math
from pathlib import Path
from statistics import NormalDist
from typing import Dict, List

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


def load_mix(name: str, directory: Path = TRAFFIC_DIR) -> Dict:
    """Read ``<directory>/<name>.json``."""
    path = Path(directory) / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} at {path}")
    return json.loads(path.read_text())


def _quantiles(n: int) -> List[float]:
    return [(i + 0.5) / n for i in range(n)]


def session_lengths(tokens: Dict, n: int) -> List[int]:
    """``n`` committed-token targets at fixed quantiles of a clipped log-normal."""
    if tokens["dist"] != "lognormal":
        raise ValueError(f"unknown token distribution {tokens['dist']!r}")
    z = NormalDist()
    mu, sigma = math.log(tokens["median"]), tokens["sigma"]
    out = [int(round(math.exp(mu + sigma * z.inv_cdf(u)))) for u in _quantiles(n)]
    return [min(max(x, tokens["min"]), tokens["max"]) for x in out]


def make_schedule(mix: Dict, seed: int) -> Dict:
    """The edge process's plan for one run, a closed loop.

    ``slots`` sessions run at once; when one ends the next length in
    ``lengths`` starts in its place.
    """
    if mix["loop"] != "closed":
        raise ValueError(f"unknown loop {mix['loop']!r}")
    rng = np.random.default_rng(seed)
    n = int(mix["session_pool"])
    lengths = [int(x) for x in rng.permutation(session_lengths(mix["tokens"], n))]
    return {"slots": int(mix["sessions"]), "lengths": lengths, "edge": dict(mix["edge"])}


def concurrency(mix: Dict) -> int:
    """Most sessions that can have a round in one dispatch."""
    return min(int(mix["sessions"]), int(mix["verifier"]["max_batch"]))
