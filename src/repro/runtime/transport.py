"""Pluggable cloud-edge transports carrying the typed wire protocol.

The runtime speaks :mod:`runtime.protocol` messages through a small
:class:`Transport` interface with two backends:

* :class:`InProcTransport` (= :class:`Channel`) — the simulated link: typed
  message *objects* are delivered with a Hockney-model delay of
  ``(α + β·wire_tokens(msg)) × time_scale`` (the model the paper measures,
  Fig. 6a), on either the wall clock or the deterministic ``VirtualClock``.
  Fault injection (``runtime.faults``) acts here, *below* the codec, on
  whole messages — the conformance suite is byte-independent of the codec.
* :class:`SocketTransport` — a real length-prefixed-frame TCP link between
  OS processes: ``protocol.encode``/``decode`` are the wire format, and a
  :class:`SocketListener` accepts connections with the ``Hello``/``Attach``
  version handshake, so ``CloudVerifier`` and ``EdgeClient`` deploy as
  genuinely separate processes like the paper's FastAPI testbed.

Fault injection on ``Channel`` has a single path: a pluggable ``faults``
hook (``runtime.faults.LinkFaults``) compiled from a declarative
``FaultScenario``.  The legacy ``ChannelConfig`` knobs (``drop_prob``, one
``outage`` window) are compiled into the same machinery at construction
(``faults.legacy_link_faults``), preserving their exact historical
semantics and seeded loss draws.  Both drive the fault-tolerance paths:
NAV timeout → local-decode fallback → re-attach.
"""

from __future__ import annotations

import dataclasses
import heapq
import itertools
import socket
import threading
from dataclasses import dataclass
from typing import Any, Callable, Deque, List, Optional, Tuple

from collections import deque

from repro.obs.trace import NULL_TRACER

from .faults import ComposedLinkFaults, legacy_link_faults
from .protocol import (
    PROTOCOL_VERSION,
    Attach,
    Hello,
    NavRequest,
    ProtocolError,
    ProtocolMessage,
    decode,
    encode,
    handshake_reply,
    wire_tokens,
)
from .simclock import SYSTEM_CLOCK

__all__ = [
    "ChannelConfig",
    "Transport",
    "Channel",
    "InProcTransport",
    "SocketTransport",
    "SocketListener",
    "connect_transport",
    "make_link",
]


@dataclass
class ChannelConfig:
    """Link parameters: Hockney cost model plus (legacy) fault knobs.

    ``alpha``/``beta`` also serve as *link hints* for scheduling (the DP
    batch planner reads them off the transport), so socket transports carry
    a config too even though their delivery time is the real network's.
    ``drop_prob``/``outage`` are compiled into the declarative fault layer
    at channel construction — see ``faults.legacy_link_faults``.
    """

    alpha: float = 0.020  # startup overhead [s]
    beta: float = 0.002  # per-token serialization [s]
    time_scale: float = 1.0  # multiply all delays (wall-clock tests use e.g. 0.01)
    drop_prob: float = 0.0  # legacy random loss (compiled to a fault phase)
    outage: Optional[Tuple[float, float]] = None  # legacy hard-down window
    seed: int = 0  # seeds the channel's private loss RNG


class Transport:
    """One direction (or one duplex link) carrying typed protocol messages.

    The surface the runtime codes against: blocking/timed ``recv``,
    fire-and-forget ``send`` returning a cost estimate, ``qsize`` for
    backlog stats, and ``close``.  Implementations expose ``cfg``
    (:class:`ChannelConfig` link hints), ``clock`` (the timing surface
    messages and timeouts run on), and a ``closed`` flag — True once the
    link is permanently gone, so receive loops can exit instead of polling
    a dead transport.
    """

    cfg: ChannelConfig
    clock: Any  # simclock surface (SystemClock / VirtualClock)
    closed: bool = False

    def send(self, msg: ProtocolMessage) -> float:
        """Enqueue ``msg`` for delivery; returns an estimated link cost [s]."""
        raise NotImplementedError  # pragma: no cover

    def recv(self, timeout: Optional[float] = None) -> Optional[ProtocolMessage]:
        """Blocking receive; ``None`` on timeout or transport close."""
        raise NotImplementedError  # pragma: no cover

    def qsize(self) -> int:
        """Messages in flight or awaiting pickup (for load/occupancy stats)."""
        raise NotImplementedError  # pragma: no cover

    def close(self) -> None:
        """Release the link; pending and future ``recv`` calls return None."""
        raise NotImplementedError  # pragma: no cover


class Channel(Transport):
    """In-process transport; delivery is delayed per the Hockney model.

    A dedicated dispatcher is unnecessary: delivery times live in an event
    heap keyed on the channel's clock, and ``recv`` waits (on virtual or
    wall time) until the head message's delivery time arrives.  Transmission
    of consecutive batches serializes exactly like a real link — the next
    batch's delivery time starts after the previous one frees the link —
    except for fault-injected *reordered* messages, which take an
    out-of-band path (extra delay, no link occupancy).
    """

    def __init__(
        self,
        cfg: ChannelConfig,
        name: str = "ch",
        clock=None,
        faults=None,
    ):
        self.cfg = cfg
        self.name = name
        self.clock = clock or SYSTEM_CLOCK
        # Single fault path: legacy ChannelConfig knobs compile into the same
        # declarative machinery as explicit FaultScenario schedules.
        legacy = legacy_link_faults(cfg.drop_prob, cfg.outage, cfg.seed, name)
        if faults is not None and legacy is not None:
            self.faults = ComposedLinkFaults(faults, legacy)
        else:
            self.faults = faults if faults is not None else legacy
        self._heap: list = []
        self._counter = itertools.count()
        self._cv = self.clock.condition()
        self._t0 = self.clock.monotonic()
        self._link_free = 0.0  # relative time the link frees up
        self.closed = False
        self.stats = {"sent": 0, "dropped": 0, "duplicated": 0, "reordered": 0}

    # ------------------------------------------------------------- sending --
    def send(self, msg: ProtocolMessage) -> float:
        """Enqueue; returns the simulated delivery delay (for diagnostics)."""
        now = self.clock.monotonic() - self._t0
        n_tokens = wire_tokens(msg)
        beta = self.cfg.beta
        if self.faults is not None:
            beta *= self.faults.beta_factor(now)
        cost = (self.cfg.alpha + beta * n_tokens) * self.cfg.time_scale
        with self._cv:
            self.stats["sent"] += 1
            start = max(now, self._link_free)
            deliver_at = start + cost
            self._link_free = deliver_at
            if self.faults is not None and self.faults.dropped(start):
                self.stats["dropped"] += 1
                self._cv.notify_all()
                return cost  # silently lost — receiver will time out
            extra = self.faults.reorder_delay(start) if self.faults is not None else 0.0
            if extra > 0.0:
                self.stats["reordered"] += 1
                # Out-of-band path: delayed past the link-serialized slot so
                # later messages can overtake it.
                deliver_at += extra
            heapq.heappush(self._heap, (deliver_at, next(self._counter), msg))
            if self.faults is not None and self.faults.duplicated(start):
                self.stats["duplicated"] += 1
                # The retransmitted copy re-traverses the link right behind
                # the original.
                dup_at = deliver_at + cost
                self._link_free = max(self._link_free, dup_at)
                heapq.heappush(self._heap, (dup_at, next(self._counter), msg))
            self._cv.notify_all()
        return cost

    # ----------------------------------------------------------- receiving --
    def recv(self, timeout: Optional[float] = None) -> Optional[ProtocolMessage]:
        """Blocking receive honoring delivery times; None on timeout/close."""
        deadline = None if timeout is None else self.clock.monotonic() + timeout
        with self._cv:
            while True:
                now = self.clock.monotonic() - self._t0
                # The 1ns slack absorbs float rounding between channels with
                # different time origins (a mid-run channel forwarding to a
                # t0=0 one can land a delivery time sub-ulp above ``now``,
                # which a virtual clock could otherwise never advance past).
                if self._heap and self._heap[0][0] <= now + 1e-9:
                    return heapq.heappop(self._heap)[2]
                if self.closed:
                    return None
                wait = None
                if self._heap:
                    wait = self._heap[0][0] - now
                if deadline is not None:
                    rem = deadline - self.clock.monotonic()
                    if rem <= 0:
                        return None
                    wait = rem if wait is None else min(wait, rem)
                self._cv.wait(timeout=wait if wait is None or wait > 0 else 0.001)

    def qsize(self) -> int:
        """Messages in flight or awaiting pickup (for load/occupancy stats)."""
        with self._cv:
            return len(self._heap)

    def close(self) -> None:
        """Close the link; blocked and future ``recv`` calls return None."""
        with self._cv:
            self.closed = True
            self._cv.notify_all()


#: The in-process backend under its interface name (``Channel`` predates it).
InProcTransport = Channel


def make_link(up_cfg: ChannelConfig, dn_cfg: ChannelConfig, clock=None) -> Tuple[Channel, Channel]:
    """(uplink edge→cloud, downlink cloud→edge)."""
    return Channel(up_cfg, "up", clock=clock), Channel(dn_cfg, "dn", clock=clock)


# --------------------------------------------------------------------------- #
# Socket backend: length-prefixed protocol frames over TCP
# --------------------------------------------------------------------------- #


def _recv_exact(sock: socket.socket, n: int, stop: Callable[[], bool]) -> Optional[bytes]:
    """Read exactly ``n`` bytes, polling ``stop``; None on EOF or stop."""
    chunks: List[bytes] = []
    got = 0
    while got < n:
        if stop():
            return None
        try:
            chunk = sock.recv(n - got)
        except socket.timeout:
            continue
        except OSError:
            return None
        if not chunk:  # orderly EOF
            return None
        chunks.append(chunk)
        got += len(chunk)
    return b"".join(chunks)


def _read_frame(sock: socket.socket, stop: Callable[[], bool]) -> Optional[ProtocolMessage]:
    """Read one length-prefixed frame and decode it; None on EOF/stop."""
    header = _recv_exact(sock, 4, stop)
    if header is None:
        return None
    size = int.from_bytes(header, "little")
    body = _recv_exact(sock, size, stop)
    if body is None:
        return None
    return decode(header + body)


class SocketTransport(Transport):
    """Duplex transport over one connected TCP socket (real processes).

    Frames are ``protocol.encode`` bytes; a background pump thread (spawned
    through the clock surface) decodes incoming frames into a queue that
    ``recv`` drains.  Used as BOTH the uplink and the downlink of a session:
    the server attaches the same instance twice and each side only sends its
    own direction.

    **Clock domains.**  ``NavRequest.deadline`` is an absolute timestamp on
    the sender's clock, which a peer process cannot compare against its own.
    The transport rebases it at the boundary: the wire carries the *relative*
    remaining budget, restored to an absolute receiver-clock deadline on
    arrival.  In-process transports never rebase (shared clock).

    Real sockets run on wall time only — pass no clock (or ``SYSTEM_CLOCK``);
    a ``VirtualClock`` is rejected because the network cannot block on
    virtual time.
    """

    #: Poll interval for the rx pump's socket timeout [s].
    POLL = 0.2

    def __init__(
        self,
        sock: socket.socket,
        cfg: Optional[ChannelConfig] = None,
        clock=None,
        name: str = "sock",
        session: Optional[int] = None,
        metrics=None,
        tracer=None,
    ):
        self.cfg = cfg or ChannelConfig()
        self.clock = clock or SYSTEM_CLOCK
        if getattr(self.clock, "virtual", False):
            raise ValueError("SocketTransport runs on wall time; VirtualClock is not supported")
        self.name = name
        self.session = session  # final id from the Attach handshake (if any)
        self.sock = sock
        self.sock.settimeout(self.POLL)
        self.closed = False
        self.stats = {"sent": 0, "received": 0, "bytes_sent": 0, "bytes_received": 0, "send_errors": 0}
        # Optional repro.obs.metrics.MetricRegistry: frame/byte counters are
        # mirrored into ``transport_*`` series labeled by link name.
        self.metrics = metrics
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self._rx: Deque[ProtocolMessage] = deque()
        self._cv = self.clock.condition()
        self._tx_lock = threading.Lock()  # rx-loop replies + dispatch share the socket
        self._pump = self.clock.spawn(self._rx_pump, name=f"{name}-pump")

    # ------------------------------------------------------------- sending --
    def send(self, msg: ProtocolMessage) -> float:
        """Frame and write ``msg``; returns the Hockney cost *estimate*.

        A send after the peer vanished is counted in ``send_errors`` and
        otherwise behaves like a dropped message (the runtime's timeout and
        failover paths own the recovery), mirroring ``Channel`` semantics —
        transports never raise into the serving loops.
        """
        if isinstance(msg, NavRequest) and msg.deadline is not None:
            # Wire deadline = relative budget; receiver re-absolutizes.
            msg = dataclasses.replace(msg, deadline=msg.deadline - self.clock.monotonic())
        frame = encode(msg)
        cost = (self.cfg.alpha + self.cfg.beta * wire_tokens(msg)) * self.cfg.time_scale
        with self._tx_lock:
            self.stats["sent"] += 1
            if self.closed:
                self.stats["send_errors"] += 1
                return cost
            try:
                self.sock.sendall(frame)
                self.stats["bytes_sent"] += len(frame)
            except OSError:
                self.stats["send_errors"] += 1
        if self.metrics is not None:
            self.metrics.counter("transport_frames_sent", "Frames written").inc(
                link=self.name
            )
            self.metrics.counter("transport_bytes_sent", "Frame bytes written").inc(
                len(frame), link=self.name
            )
        if self.tracer.enabled:
            # Wire occupancy estimate: the Hockney cost past the write time.
            t_tx = self.clock.monotonic()
            self.tracer.add(
                "frame", t_tx, t_tx + cost, link=self.name, bytes=len(frame)
            )
        return cost

    # ----------------------------------------------------------- receiving --
    def _rx_pump(self) -> None:
        try:
            while not self.closed:
                try:
                    msg = _read_frame(self.sock, lambda: self.closed)
                except ProtocolError:  # corrupt/unknown frame: the stream is
                    break  # unrecoverable — tear the link down
                if msg is None:  # EOF or stop: the link is gone
                    break
                if isinstance(msg, NavRequest) and msg.deadline is not None:
                    msg = dataclasses.replace(
                        msg, deadline=self.clock.monotonic() + msg.deadline
                    )
                with self._cv:
                    self.stats["received"] += 1
                    self._rx.append(msg)
                    self._cv.notify_all()
                if self.metrics is not None:
                    self.metrics.counter(
                        "transport_frames_received", "Frames decoded"
                    ).inc(link=self.name)
        finally:
            # ALWAYS mark closed (even on unexpected errors) so recv() callers
            # and liveness polls see the link as gone instead of wedging.
            with self._cv:
                self.closed = True
                self._cv.notify_all()

    def recv(self, timeout: Optional[float] = None) -> Optional[ProtocolMessage]:
        """Pop the next decoded message; None on timeout or closed link."""
        deadline = None if timeout is None else self.clock.monotonic() + timeout
        with self._cv:
            while True:
                if self._rx:
                    return self._rx.popleft()
                if self.closed:
                    return None
                wait = None
                if deadline is not None:
                    wait = deadline - self.clock.monotonic()
                    if wait <= 0:
                        return None
                self._cv.wait(timeout=wait)

    def qsize(self) -> int:
        """Decoded messages awaiting pickup."""
        with self._cv:
            return len(self._rx)

    def close(self) -> None:
        """Tear down the socket; the pump exits and ``recv`` returns None."""
        with self._cv:
            if self.closed:
                return
            self.closed = True
            self._cv.notify_all()
        try:
            self.sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        try:
            self.sock.close()
        except OSError:
            pass


class SocketListener:
    """Server-side accept loop with the ``Hello``/``Attach`` handshake.

    Accepts TCP connections, performs version negotiation (rejecting
    mismatched clients with a diagnostic ``Attach`` before closing them),
    remaps colliding session ids to the next free one, and hands each
    accepted session's :class:`SocketTransport` to ``on_session(session,
    transport)`` — typically ``CloudVerifier.attach(session, t, t)``.

    ``port=0`` binds an ephemeral port; read it back from ``self.port``.
    """

    def __init__(
        self,
        on_session: Callable[[int, SocketTransport], None],
        host: str = "127.0.0.1",
        port: int = 0,
        cfg: Optional[ChannelConfig] = None,
        clock=None,
        handshake_timeout: float = 5.0,
    ):
        self.on_session = on_session
        self.cfg = cfg or ChannelConfig()
        self.clock = clock or SYSTEM_CLOCK
        if getattr(self.clock, "virtual", False):
            raise ValueError("SocketListener runs on wall time; VirtualClock is not supported")
        self.handshake_timeout = handshake_timeout
        self.closed = False
        self.transports: List[SocketTransport] = []
        self.stats = {"accepted": 0, "rejected": 0}
        self._sessions: set = set()
        self._lsock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._lsock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._lsock.bind((host, port))
        self._lsock.listen()
        self._lsock.settimeout(SocketTransport.POLL)
        self.host, self.port = self._lsock.getsockname()[:2]
        self._acceptor = self.clock.spawn(self._accept_loop, name="socket-accept")

    def _handshake(self, conn: socket.socket) -> Optional[SocketTransport]:
        """Run Hello/Attach on a fresh connection; None when rejected."""
        conn.settimeout(SocketTransport.POLL)
        deadline = self.clock.monotonic() + self.handshake_timeout
        hello = _read_frame(
            conn, lambda: self.closed or self.clock.monotonic() > deadline
        )
        if not isinstance(hello, Hello):
            conn.close()
            self.stats["rejected"] += 1
            return None
        # Dead links release their session ids: a re-dial for the same
        # session (router migration / client re-attach) is not a collision.
        for t in [t for t in self.transports if t.closed]:
            self.transports.remove(t)
            self._sessions.discard(t.session)
        session = hello.session
        while session in self._sessions:  # collision: remap to the next free id
            session += 1
        reply = handshake_reply(hello, session=session)
        if not reply.accepted:
            # Counted before the refusal is sent: a client that reads it
            # then finds the rejection in ``stats``.
            self.stats["rejected"] += 1
        try:
            conn.sendall(encode(reply))
        except OSError:
            conn.close()
            if reply.accepted:
                self.stats["rejected"] += 1
            return None
        if not reply.accepted:  # version mismatch: hang up
            conn.close()
            return None
        self._sessions.add(session)
        self.stats["accepted"] += 1
        return SocketTransport(
            conn, cfg=self.cfg, clock=self.clock, name=f"srv-{session}", session=session
        )

    def _accept_loop(self) -> None:
        while not self.closed:
            try:
                conn, _addr = self._lsock.accept()
            except socket.timeout:
                continue
            except OSError:
                break
            try:
                transport = self._handshake(conn)
            except ProtocolError:
                conn.close()
                self.stats["rejected"] += 1
                continue
            if transport is None:
                continue
            self.transports.append(transport)
            try:
                self.on_session(transport.session, transport)
            except Exception:
                # Admission refusal (draining verifier, full fleet): hang up
                # on this client; the listener keeps serving others.
                transport.close()
                self.stats["rejected"] += 1

    def close(self) -> None:
        """Stop accepting and close every accepted transport."""
        self.closed = True
        try:
            self._lsock.close()
        except OSError:
            pass
        for t in self.transports:
            t.close()


def connect_transport(
    host: str,
    port: int,
    session: int = 0,
    cfg: Optional[ChannelConfig] = None,
    clock=None,
    timeout: float = 10.0,
    version: int = PROTOCOL_VERSION,
) -> SocketTransport:
    """Dial a :class:`SocketListener` and complete the attach handshake.

    Sends ``Hello`` and waits for the server's ``Attach``; raises
    :class:`~repro.runtime.protocol.ProtocolError` when the server rejects
    the protocol version (carrying the server's diagnostic reason).  The
    returned transport's ``session`` is the server-assigned id.
    """
    sock = socket.create_connection((host, port), timeout=timeout)
    sock.settimeout(SocketTransport.POLL)
    clk = clock or SYSTEM_CLOCK
    deadline = clk.monotonic() + timeout
    try:
        sock.sendall(encode(Hello(session=session, version=version)))
        reply = _read_frame(sock, lambda: clk.monotonic() > deadline)
    except OSError as e:
        sock.close()
        raise ProtocolError(f"attach handshake failed: {e}") from e
    if not isinstance(reply, Attach):
        sock.close()
        raise ProtocolError(f"expected Attach during handshake, got {type(reply).__name__}")
    if not reply.accepted:
        sock.close()
        raise ProtocolError(f"attach rejected: {reply.reason}")
    return SocketTransport(
        sock, cfg=cfg, clock=clock, name=f"cli-{reply.session}", session=reply.session
    )
