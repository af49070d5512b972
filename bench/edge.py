"""Edge side of a benchmark run, in a process of its own.

Started by the harness with ``JAX_PLATFORMS=cpu`` so that it never touches
the chip and does not share the verifier's interpreter lock.  Protocol on
its standard streams:

1. argv[1] is the schedule (JSON, from ``bench.traffic.make_schedule``) and
   argv[2] the launcher seed the draft model uses.  After its imports it
   prints ``READY``.
2. It reads one line ``GO <host> <port> <t_stop>``: dial the verifier at
   ``host:port`` and run the schedule.  ``t_stop`` is on
   ``time.monotonic()``, which every process of the machine shares: no
   round starts after it, and rounds already sent wait for their result.
3. It prints one JSON line with every round and session it ran, and exits.

Each session is the program's own ``EdgeClient`` over ``connect_transport``
with an ``OracleDraft``, as ``launch/serve.py`` streams one.  Between the
client and its socket sits ``_TimedLink``, the benchmark's own clock: it
stamps each ``NavRequest`` sent and the matching ``NavResult`` received,
and keeps the drafted tokens of each round for the correctness check.
"""

from __future__ import annotations

import json
import sys
import threading
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))

from repro.runtime import (  # noqa: E402
    ChannelConfig,
    Detach,
    EdgeClient,
    EdgeConfig,
    OracleDraft,
    connect_transport,
)
from repro.runtime.protocol import DraftFragment, NavRequest, NavResult  # noqa: E402


class _Stop(Exception):
    """Raised inside a client once the run's stop time has passed."""


class _TimedLink:
    """Duplex transport wrapper: the benchmark's stamps on every round."""

    def __init__(self, inner, t_stop: float, rounds: list):
        self.inner = inner
        self.t_stop = t_stop
        self.rounds = rounds
        self.drafts: dict = {}
        self.pending: dict = {}

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def send(self, msg):
        now = time.monotonic()
        if now >= self.t_stop and not isinstance(msg, Detach):
            raise _Stop()
        if isinstance(msg, DraftFragment):
            self.drafts.setdefault(msg.round, []).extend(int(t) for t in msg.tokens)
        elif isinstance(msg, NavRequest):
            self.pending[msg.seq] = {
                "session": int(msg.session),
                "round": int(msg.round),
                "pos": int(msg.pos),
                "tokens": self.drafts.pop(msg.round, [])[: msg.n_tokens],
                "t_req": now,
            }
        return self.inner.send(msg)

    def recv(self, timeout=None):
        msg = self.inner.recv(timeout=timeout)
        if isinstance(msg, NavResult):
            rec = self.pending.pop(msg.seq, None)
            if rec is not None:
                rec.update(t_res=time.monotonic(), n_accepted=int(msg.n_accepted),
                           correction=int(msg.correction))
                self.rounds.append(rec)
        return msg


def run_session(host, port, sid, n_tokens, edge, seed, t_stop, out):
    """One edge session: dial, stream until ``n_tokens`` commit or stop, detach."""
    link = connect_transport(
        host, port, session=sid, cfg=ChannelConfig(alpha=edge["alpha"], beta=edge["beta"])
    )
    rounds: list = []
    timed = _TimedLink(link, t_stop, rounds)
    cfg = EdgeConfig(gamma=edge["gamma"], window=edge["window"], nav_timeout=edge["nav_timeout"])
    client = EdgeClient(link.session, timed, timed, cfg, draft=OracleDraft(seed=seed))
    stopped = False
    try:
        client.run(n_tokens)
    except _Stop:
        stopped = True
    finally:
        client.seq += 1
        try:
            link.send(Detach(session=link.session, seq=client.seq))
        finally:
            link.close()
    # Rounds still awaiting a result when the client gave up on them.
    lost = [dict(r, t_res=None) for r in timed.pending.values()]
    out["rounds"].extend(rounds + lost)
    out["sessions"].append({
        "session": int(link.session), "target": int(n_tokens), "stopped": stopped,
        "committed": len(client.tokens), "failovers": int(client.stats["failovers"]),
    })


def main() -> int:
    sched = json.loads(sys.argv[1])
    seed = int(sys.argv[2])
    edge = sched["edge"]
    print("READY", flush=True)
    line = sys.stdin.readline().split()
    if not line or line[0] != "GO":
        return 2
    host, port, t_stop = line[1], int(line[2]), float(line[3])
    out = {"rounds": [], "sessions": [], "start_lag_s": [], "errors": []}
    lock = threading.Lock()
    lengths = iter(sched["lengths"])
    next_sid = iter(range(1, 1 << 30))

    def take():
        with lock:
            return next(next_sid), next(lengths, None)

    def guarded(sid, n, due):
        out["start_lag_s"].append(time.monotonic() - due)
        try:
            run_session(host, port, sid, n, edge, seed, t_stop, out)
        except Exception as e:  # reported, and the run is then not correct
            out["errors"].append(f"session {sid}: {type(e).__name__}: {e}")

    def slot():
        due = time.monotonic()
        while time.monotonic() < t_stop:
            sid, n = take()
            if n is None:
                out["errors"].append("the schedule ran out of sessions")
                return
            guarded(sid, n, due)
            due = time.monotonic()

    threads = [threading.Thread(target=slot, daemon=True) for _ in range(sched["slots"])]
    for t in threads:
        t.start()
    grace = t_stop + 2 * edge["nav_timeout"] + 10.0
    for t in threads:
        t.join(max(grace - time.monotonic(), 0.0))
    alive = sum(t.is_alive() for t in threads)
    if alive:
        out["errors"].append(f"{alive} session threads still running after the stop")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
