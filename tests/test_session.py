"""Registration leases, session ids, and reconnect/resume (DESIGN.md §12).

Two layers under test:

* ``PartyRegistry`` — the pure lease state machine (injected
  timestamps, no sockets, no sleeping);
* the coordinator's wire behaviour — raw-socket "parties" exercise
  HELLO/WELCOME registration, duplicate rejection, resume after a
  reconnect, the typed :class:`StaleSessionError` rejection of
  superseded/expired sessions, and the regression that a *silent*
  party on a live socket (e.g. mid-JIT) must never be evicted by
  lease expiry — frames on the authenticated connection are liveness
  evidence and renew the lease instead of tripping over it.
"""

import asyncio
import socket
import threading
import time

import pytest

from repro.net import PartyRegistry, StaleSessionError
from repro.net.config import WireConfig
from repro.net.coordinator import Coordinator
from repro.net.wire import Frame, FrameReader, MsgType, encode_frame
from repro.net import codec


# ---------------------------------------------------------------------------
# PartyRegistry: the lease state machine (unit, no sockets)
# ---------------------------------------------------------------------------

def test_registry_session_layout_and_supersede():
    reg = PartyRegistry(4, lease_s=30.0)
    s0 = reg.register(0, now=0.0)
    assert s0 == 0x1                       # gen 0, pid 0 -> (0<<20)|1
    assert reg.session_of(0) == s0
    s1 = reg.register(0, now=1.0)          # re-register bumps generation
    assert s1 == (1 << 20) | 1
    reg.validate(0, s1, now=1.0)
    with pytest.raises(StaleSessionError, match="stale session"):
        reg.validate(0, s0, now=1.0)
    with pytest.raises(StaleSessionError, match="no registration"):
        reg.validate(1, 0x2, now=1.0)
    with pytest.raises(ValueError):
        reg.register(4, now=0.0)           # outside range(n)


def test_registry_resume_renews_and_rejects_stale():
    reg = PartyRegistry(2, lease_s=10.0)
    s0 = reg.register(0, now=0.0)
    assert reg.resume(0, s0, now=5.0) == s0
    assert reg.live(0, now=14.0)           # resumed at 5 -> expires 15
    with pytest.raises(StaleSessionError, match="expired"):
        reg.resume(0, s0, now=99.0)
    s1 = reg.register(0, now=100.0)
    with pytest.raises(StaleSessionError, match="stale"):
        reg.resume(0, s0, now=100.0)
    assert reg.resume(0, s1, now=100.0) == s1


def test_registry_resume_never_registered_is_typed():
    """Regression: resuming a pid that never registered must raise the
    typed StaleSessionError (→ ERROR frame on the wire), not leak a
    bare KeyError out of the lease table."""
    reg = PartyRegistry(4, lease_s=30.0)
    with pytest.raises(StaleSessionError, match="no registration"):
        reg.resume(2, (1 << 20) | 3, now=0.0)
    # the failed resume must not have materialized a lease
    assert reg.session_of(2) is None
    assert reg.register(2, now=0.0) == 0x3


def test_registry_validate_without_expiry_enforcement():
    """The coordinator's per-frame gate: identity always checked,
    expiry not — a quiet-but-connected party (long local JIT) must not
    be evicted by its own silence."""
    reg = PartyRegistry(2, lease_s=1.0)
    s0 = reg.register(0, now=0.0)
    with pytest.raises(StaleSessionError, match="expired"):
        reg.validate(0, s0, now=50.0)
    reg.validate(0, s0, now=50.0, enforce_expiry=False)   # identity ok
    s1 = reg.register(0, now=50.0)
    with pytest.raises(StaleSessionError, match="stale"):
        # superseded stays fatal even without expiry enforcement
        reg.validate(0, s0, now=50.0, enforce_expiry=False)
    reg.validate(0, s1, now=999.0, enforce_expiry=False)


def test_registry_eligible_and_expire_with_injected_clock():
    reg = PartyRegistry(8, lease_s=10.0)
    for pid in range(5):
        reg.register(pid, now=float(pid))    # expiries 10..14
    assert reg.eligible(now=9.0) == set(range(5))
    assert reg.eligible(now=12.5) == {3, 4}
    assert reg.expire(now=12.5) == {0, 1, 2}
    assert len(reg) == 2
    reg.renew(3, now=12.5)                   # renewal extends to 22.5
    assert reg.eligible(now=20.0) == {3}


def test_registry_infinite_lease():
    reg = PartyRegistry(2, lease_s=None)
    s0 = reg.register(0, now=0.0)
    reg.validate(0, s0, now=1e12)
    assert reg.eligible(now=1e12) == {0}


# ---------------------------------------------------------------------------
# Wire behaviour: raw-socket parties against a live coordinator
# ---------------------------------------------------------------------------

class _Hub:
    """A Coordinator on a background event loop, no party workers."""

    def __init__(self, n=2, lease_s=30.0):
        cfg = WireConfig(n=n, m=min(3, n), lease_s=lease_s,
                         deadline_s=None)
        self.co = Coordinator(cfg)
        self.loop = asyncio.new_event_loop()
        self.thread = threading.Thread(target=self.loop.run_forever,
                                       daemon=True)
        self.thread.start()
        self.port = self._run(self.co.start("127.0.0.1", 0))

    def _run(self, coro, timeout=10.0):
        return asyncio.run_coroutine_threadsafe(
            coro, self.loop).result(timeout)

    def close(self):
        self._run(self.co.stop())
        self.loop.call_soon_threadsafe(self.loop.stop)
        self.thread.join(timeout=5)
        self.loop.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def wait_conn_dead(self, pid, timeout=5.0):
        """Wait until the coordinator noticed ``pid``'s EOF."""
        t0 = time.monotonic()
        while time.monotonic() - t0 < timeout:
            conn = self.co._conns.get(pid)
            if conn is None or not conn.alive:
                return
            time.sleep(0.01)
        raise AssertionError(f"party {pid} connection never died")


class _RawParty:
    """Blocking-socket party speaking just enough of the protocol."""

    def __init__(self, port):
        self.sock = socket.create_connection(("127.0.0.1", port),
                                             timeout=10.0)
        self.reader = FrameReader()

    def send(self, frame):
        self.sock.sendall(encode_frame(frame))

    def recv(self, timeout=10.0):
        """Next frame, or None on EOF."""
        self.sock.settimeout(timeout)
        while True:
            try:
                data = self.sock.recv(65536)
            except ConnectionError:
                data = b""
            if not data:
                self.reader.eof()
                return None
            frames = self.reader.feed(data)
            if frames:
                return frames[0]

    def hello(self, pid, session=0):
        self.send(Frame(MsgType.HELLO, src=pid, session=session))
        return self.recv()

    def close(self):
        self.sock.close()


@pytest.mark.net
def test_wire_register_resume_and_stale_session_rejection():
    with _Hub(n=2) as hub:
        p1 = _RawParty(hub.port)
        w = p1.hello(0)
        assert w.msg_type == MsgType.WELCOME
        s0 = w.session
        assert s0 == 0x1
        # the WELCOME payload carries the federation config
        assert codec.decode_json(w.payload)["n"] == 2

        # duplicate HELLO while the first socket is alive: rejected,
        # the original connection keeps its lease
        dup = _RawParty(hub.port)
        assert dup.hello(0) is None
        dup.close()
        assert hub.co.registry.session_of(0) == s0

        # drop and re-register fresh: the generation bumps and the old
        # session id becomes stale
        p1.close()
        hub.wait_conn_dead(0)
        p2 = _RawParty(hub.port)
        s1 = p2.hello(0).session
        assert s1 == (1 << 20) | 1

        # reconnect presenting the superseded session: typed ERROR
        p2.close()
        hub.wait_conn_dead(0)
        p3 = _RawParty(hub.port)
        err = p3.hello(0, session=s0)
        assert err.msg_type == MsgType.ERROR
        assert "stale" in codec.decode_json(err.payload)["error"]
        assert p3.recv() is None               # and the socket closes
        p3.close()

        # reconnect presenting the *current* session: resumed, same id
        p4 = _RawParty(hub.port)
        assert p4.hello(0, session=s1).session == s1
        p4.close()


@pytest.mark.net
def test_wire_stop_returns_after_drop_and_reregister():
    """Regression: the coordinator closes a connection whose read loop
    ended.  Server.wait_closed() waits for every accepted connection
    (Python >= 3.12), so a dropped socket left open hung stop()."""
    with _Hub(n=2) as hub:
        p1 = _RawParty(hub.port)
        assert p1.hello(0).msg_type == MsgType.WELCOME
        p1.close()
        hub.wait_conn_dead(0)
        p2 = _RawParty(hub.port)
        assert p2.hello(0).msg_type == MsgType.WELCOME
        t0 = time.monotonic()
        hub._run(hub.co.stop(), timeout=2.0)
        assert time.monotonic() - t0 < 2.0
        p2.close()


@pytest.mark.net
def test_wire_resume_never_registered_pid_typed_error():
    """Raw-socket regression for the coordinator's resume path: a
    HELLO presenting a session id for a pid that never registered gets
    a typed ERROR frame ("no registration") and a clean close — the
    lease table is untouched, so the pid can still register fresh."""
    with _Hub(n=2) as hub:
        p = _RawParty(hub.port)
        err = p.hello(1, session=(3 << 20) | 2)
        assert err.msg_type == MsgType.ERROR
        assert "no registration" in codec.decode_json(err.payload)["error"]
        assert p.recv() is None               # coordinator closed it
        p.close()
        assert hub.co.registry.session_of(1) is None
        p2 = _RawParty(hub.port)
        w = p2.hello(1)
        assert w.msg_type == MsgType.WELCOME
        assert w.session == 0x2
        p2.close()


@pytest.mark.net
def test_wire_resume_after_lease_expiry_rejected():
    with _Hub(n=2, lease_s=0.05) as hub:
        p1 = _RawParty(hub.port)
        s0 = p1.hello(0).session
        p1.close()
        hub.wait_conn_dead(0)
        time.sleep(0.12)                      # let the lease lapse
        p2 = _RawParty(hub.port)
        err = p2.hello(0, session=s0)
        assert err.msg_type == MsgType.ERROR
        assert "expired" in codec.decode_json(err.payload)["error"]
        p2.close()


@pytest.mark.net
def test_wire_silent_party_on_live_socket_survives_expiry():
    """Regression: a party silent past lease_s on a still-open socket
    (long local JIT compile) must not be evicted when it next speaks —
    the frame renews the lease instead of raising StaleSessionError."""
    with _Hub(n=2, lease_s=0.1) as hub:
        p1 = _RawParty(hub.port)
        s0 = p1.hello(0).session
        time.sleep(0.3)                       # lease long expired
        p1.send(Frame(MsgType.READY, src=0, session=s0))
        # the frame was accepted: connection stays open (no EOF) and
        # the lease was renewed back into the eligible pool
        t0 = time.monotonic()
        while 0 not in hub.co._ready and time.monotonic() - t0 < 5:
            time.sleep(0.01)
        assert 0 in hub.co._ready
        conn = hub.co._conns.get(0)
        assert conn is not None and conn.alive
        assert 0 in hub.co.registry.eligible(
            hub.co.clock.monotonic())
        p1.close()
