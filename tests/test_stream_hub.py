"""The front door's one fan-out reader (serving/http_frontend.py
``_StreamHub``): one thread follows every live token stream with one
multi-key XREAD and writes each client's events itself.  Pinned here,
against a bare broker with the pump's XADDs written by the test: every
stream gets each token once, in index order, terminal last; one writer
thread whatever the number of streams; a client that stops reading does
not hold up the others and is let go after the stream's timeout; a
disconnect cancels; ``restart``, ``ping``, the timeout's ``error`` and
the terminal kinds keep their events; a failed hub hands every stream
an ``error`` event; TLS streams.  The real stack's SSE, disconnect,
mid-stream cancel and graceful-kill paths are in tests/test_frontdoor.py."""

import datetime
import json
import socket
import ssl
import time

import pytest

from analytics_zoo_tpu.serving import http_frontend as hf
from analytics_zoo_tpu.serving.queues import CANCEL_STREAM
from analytics_zoo_tpu.serving.resp import RespClient, RespServer
from analytics_zoo_tpu.serving.telemetry import FanoutClock


class _Door:
    """A bare broker (on a clock of its own) and a detached front door."""

    def __init__(self, timeout=30.0, **kw):
        self.broker_clock = FanoutClock()
        self.broker = RespServer(port=0, fanout=self.broker_clock).start()
        self.fe = hf.HttpFrontend(redis_port=self.broker.port,
                                  timeout=timeout, **kw).start()
        self.c = RespClient(port=self.broker.port)

    def close(self):
        self.c.close()
        self.fe.stop()
        self.broker.stop()

    def open(self, uri, rcvbuf=None, wrap=None):
        """POST a streaming request; return its client once the ``start``
        event has arrived (the stream is with the hub)."""
        s = socket.socket()
        if rcvbuf:
            s.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, rcvbuf)
        s.settimeout(30)
        s.connect(("127.0.0.1", self.fe.port))
        if wrap is not None:
            s = wrap(s)
        body = json.dumps({"prompt": [1, 2, 3], "stream": True})
        s.sendall((f"POST /v1/generate HTTP/1.1\r\nHost: x\r\n"
                   f"X-Request-Id: {uri}\r\nContent-Type: application/json"
                   f"\r\nContent-Length: {len(body)}\r\n\r\n{body}"
                   ).encode())
        c = _Client(s)
        assert c.events(until="start")[-1] == ("start", {"uri": uri})
        return c

    def tokens(self, uris, idx, val=None, stamp=True):
        """One pump flush: token ``idx`` of each uri, in one pipeline."""
        st = [b"s", int(time.monotonic() * 1e6)] if stamp else []
        self.c.pipeline([("XADD", f"tok:{u}", "*", "i", idx, "t",
                          idx + 100 if val is None else val, *st)
                         for u in uris])

    def mark(self, uri, *fields):
        self.c.execute("XADD", f"tok:{uri}", "*", *fields)

    def cancels(self):
        return [f[1].decode() for _, f in
                self.c.execute("XRANGE", CANCEL_STREAM, "-", "+")]


class _Client:
    """An SSE client on a raw socket: ``events`` reads frames to EOF, or
    to the first of kind ``until``, as [(event, data)], a ``: ping``
    comment as ("ping", None); what it read past that stays for the
    next call."""

    def __init__(self, sock):
        self.sock, self.buf = sock, b""

    def events(self, until=None, timeout=30.0, pings=True):
        self.sock.settimeout(timeout)
        out = []
        while True:
            while b"\n\n" in self.buf:
                frame, self.buf = self.buf.split(b"\n\n", 1)
                lines = frame.decode().split("\n")
                if lines[0].startswith(": ping"):
                    if pings:
                        out.append(("ping", None))
                    continue
                ev = [ln[7:] for ln in lines if ln.startswith("event: ")]
                data = [ln[6:] for ln in lines if ln.startswith("data: ")]
                if ev:
                    out.append((ev[0], json.loads(data[0])))
                    if ev[0] == until:
                        return out
            chunk = self.sock.recv(65536)
            if not chunk:
                return out
            self.buf += chunk

    def close(self):
        self.sock.close()


def _live_rows(clock, kind):
    return len(clock._cols[kind][0]) - len(clock._free[kind])


@pytest.fixture
def door():
    d = _Door()
    try:
        yield d
    finally:
        d.close()


def test_32_streams_get_every_token_once_in_order_terminal_last(door):
    uris = [f"s{i}" for i in range(32)]
    socks = {u: door.open(u) for u in uris}
    cur = door.fe.fanout.cursor()
    for k in range(16):
        door.tokens(uris, k)
        if k % 4 == 3:      # a preempted row re-emits what it had sent
            door.tokens(uris[::3], k - 1)
    door.c.pipeline([("XADD", f"tok:{u}", "*", "done", "1") for u in uris])
    for u, s in socks.items():
        evs = s.events(pings=False)
        s.close()
        assert evs[-1] == ("done", {"uri": u}), (u, evs)
        assert [d["index"] for _, d in evs[:-1]] == list(range(16)), evs
        assert [d["token"] for _, d in evs[:-1]] == [100 + k
                                                    for k in range(16)]
    d = cur.take()
    assert d["fanout_events"] == 32 * 17
    assert d["fanout_lag_n"] == 32 * 16
    # one read for many streams: a wake brings many events
    assert 1 <= d["fanout_wakes"] <= d["fanout_events"] // 4, d
    deadline = time.monotonic() + 5
    while any(door.c.execute("XLEN", f"tok:{u}") for u in uris):
        assert time.monotonic() < deadline      # the hub deleted each key
        time.sleep(0.02)


def test_one_writer_thread_whatever_the_streams(door):
    """The fan-out's thread count does not grow with the streams: one
    door account, the hub's, for 2 streams or 32, and the broker
    connections the front door holds are bounded by its pool, not by
    the streams (each stream held its own reading connection and
    writer thread before the hub)."""
    bound = 2 * door.fe._pool_max + 2       # the pool, the hub, the test
    socks = {}
    for n in (2, 32):
        socks.update({f"w{i}": door.open(f"w{i}")
                      for i in range(len(socks), n)})
        assert _live_rows(door.fe.fanout, "door") == 1
        assert door.fe._hub._running
        deadline = time.monotonic() + 10
        while _live_rows(door.broker_clock, "broker") > bound:
            assert time.monotonic() < deadline, _live_rows(
                door.broker_clock, "broker")
            time.sleep(0.05)
    door.tokens(list(socks), 0)
    door.c.pipeline([("XADD", f"tok:{u}", "*", "done", "1")
                     for u in socks])
    for u, s in socks.items():
        assert [e for e, _ in s.events(pings=False)] == ["token", "done"]
        s.close()


def test_a_client_that_stops_reading_does_not_hold_up_the_others(
        monkeypatch):
    """A stalled client parks its stream (its key leaves the read, so the
    broker holds what it has not taken); another stream's tokens still
    leave at once; after the stream's timeout with nothing taken the
    stalled one counts as a disconnect and its request is cancelled."""
    follow = hf._StreamHub.follow

    def small_buffers(self, uri, sock, client):
        # a small send buffer: the stall shows after some hundreds of
        # tokens and not after the megabytes the kernel would buffer
        if uri == "slow":
            sock.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 4096)
        return follow(self, uri, sock, client)

    monkeypatch.setattr(hf._StreamHub, "follow", small_buffers)
    d = _Door(timeout=3.0)
    try:
        slow = d.open("slow", rcvbuf=4096)
        d.c.pipeline([("XADD", "tok:slow", "*", "i", k, "t", 7)
                      for k in range(5000)])
        time.sleep(0.3)
        fast = d.open("fast")
        got = []
        for k in range(5):
            t0 = time.monotonic()
            d.tokens(["fast"], k)
            got += fast.events(until="token", pings=False)
            assert time.monotonic() - t0 < 1.0     # the stall limit is 3
        d.mark("fast", "done", "1")
        evs = got + fast.events(pings=False)
        assert [e for e, _ in evs] == ["token"] * 5 + ["done"]
        deadline = time.monotonic() + 10
        while d.fe.c_disconnects.value < 1:
            assert time.monotonic() < deadline
            time.sleep(0.05)
        assert d.cancels() == ["slow"]
        slow.close()
        fast.close()
    finally:
        d.close()


def test_a_disconnect_cancels_the_request(door):
    s = door.open("gone")
    door.tokens(["gone"], 0)
    assert [e for e, _ in s.events(until="token")] == ["token"]
    s.sock.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                 b"\x01\x00\x00\x00\x00\x00\x00\x00")
    s.close()                               # a hard drop
    deadline = time.monotonic() + 10
    k = 1
    while door.fe.c_disconnects.value < 1:
        assert time.monotonic() < deadline
        door.tokens(["gone"], k)
        k += 1
        time.sleep(0.05)
    deadline = time.monotonic() + 5
    while door.cancels() != ["gone"]:
        assert time.monotonic() < deadline, door.cancels()
        time.sleep(0.05)


@pytest.mark.parametrize("case", ["restart", "ping", "timeout",
                                  "deadline", "cancelled"])
def test_restart_ping_timeout_and_terminals_keep_their_events(case):
    d = _Door(timeout=2.0)
    d.fe._hub.poll_s = 0.3
    try:
        s = d.open("r")
        if case == "restart":
            d.tokens(["r"], 0)
            d.tokens(["r"], 1)
            d.mark("r", "restart", "1")
            for k in (0, 1, 2, 1):          # the replay, then a re-emission
                d.tokens(["r"], k, val=200 + k)
            d.mark("r", "done", "1")
            evs = s.events(pings=False)
            assert evs == [
                ("token", {"index": 0, "token": 100}),
                ("token", {"index": 1, "token": 101}),
                ("restart", {"uri": "r", "attempt": 1}),
                ("token", {"index": 0, "token": 200}),
                ("token", {"index": 1, "token": 201}),
                ("token", {"index": 2, "token": 202}),
                ("done", {"uri": "r"})]
        elif case == "ping":
            d.tokens(["r"], 0)
            time.sleep(1.2)                 # quiet for four polls
            d.mark("r", "done", "1")
            evs = s.events()
            kinds = [e for e, _ in evs]
            assert kinds[:2] == ["token", "ping"] and kinds[-1] == "done"
            assert 2 <= kinds.count("ping") <= 5, kinds
        elif case == "timeout":
            t0 = time.monotonic()
            d.tokens(["r"], 0)
            evs = s.events(pings=False)
            assert evs == [("token", {"index": 0, "token": 100}),
                           ("error", {"uri": "r", "error": "no stream "
                                      "event for 'r' in 2.0s"})]
            assert 1.9 <= time.monotonic() - t0 < 4.0
            deadline = time.monotonic() + 5
            while d.c.execute("XLEN", "tok:r"):     # the hub deletes the
                assert time.monotonic() < deadline  # key before its next
                time.sleep(0.02)                    # read
        elif case == "deadline":
            d.mark("r", "error", "deadline_exceeded: arrived too late")
            assert s.events(pings=False) == [("deadline_exceeded", {
                "uri": "r", "error": "deadline_exceeded: arrived too late"})]
        else:
            d.tokens(["r"], 0)
            d.mark("r", "cancelled", "1")
            d.tokens(["r"], 1)              # behind the terminal: never sent
            assert s.events(pings=False) == [
                ("token", {"index": 0, "token": 100}),
                ("cancelled", {"uri": "r"})]
        s.close()
    finally:
        d.close()


def test_a_second_stream_under_a_live_request_id_gets_an_error(door):
    first = door.open("twice")
    second = door.open("twice")
    assert second.events(pings=False) == [("error", {
        "uri": "twice", "error": "a stream for 'twice' is already open"})]
    door.mark("twice", "done", "1")
    assert first.events(pings=False) == [("done", {"uri": "twice"})]
    first.close()
    second.close()


def test_a_failed_hub_hands_every_stream_an_error(door, monkeypatch):
    def boom(*a, **kw):
        raise RuntimeError("boom")

    socks = {u: door.open(u) for u in ("f0", "f1", "f2")}
    with monkeypatch.context() as m:
        m.setattr(hf._StreamHub, "_write", boom)
        door.tokens(["f1"], 0)
        for u, s in socks.items():
            assert s.events(pings=False) == [("error", {
                "uri": u, "error": "stream hub failed: RuntimeError: boom"})]
            s.close()
    deadline = time.monotonic() + 5
    while door.fe._hub._running:
        assert time.monotonic() < deadline
        time.sleep(0.02)
    # the next stream starts a new hub
    s = door.open("after")
    door.tokens(["after"], 0)
    door.mark("after", "done", "1")
    assert [e for e, _ in s.events(pings=False)] == ["token", "done"]
    s.close()


def test_a_tls_front_door_streams(tmp_path):
    from cryptography import x509
    from cryptography.hazmat.primitives import hashes, serialization
    from cryptography.hazmat.primitives.asymmetric import ec
    from cryptography.x509.oid import NameOID

    key = ec.generate_private_key(ec.SECP256R1())
    name = x509.Name([x509.NameAttribute(NameOID.COMMON_NAME, "localhost")])
    now = datetime.datetime.now(datetime.timezone.utc)
    cert = (x509.CertificateBuilder().subject_name(name).issuer_name(name)
            .public_key(key.public_key())
            .serial_number(x509.random_serial_number())
            .not_valid_before(now - datetime.timedelta(days=1))
            .not_valid_after(now + datetime.timedelta(days=1))
            .sign(key, hashes.SHA256()))
    certfile, keyfile = tmp_path / "c.pem", tmp_path / "k.pem"
    certfile.write_bytes(cert.public_bytes(serialization.Encoding.PEM))
    keyfile.write_bytes(key.private_bytes(
        serialization.Encoding.PEM, serialization.PrivateFormat.PKCS8,
        serialization.NoEncryption()))
    ctx = ssl.create_default_context()
    ctx.check_hostname = False
    ctx.verify_mode = ssl.CERT_NONE
    d = _Door(certfile=str(certfile), keyfile=str(keyfile))
    try:
        s = d.open("tls", wrap=lambda raw: ctx.wrap_socket(raw))
        for k in range(300):                # several TLS records a read
            d.tokens(["tls"], k)
        d.mark("tls", "done", "1")
        evs = s.events(pings=False)
        assert [x["index"] for _, x in evs[:-1]] == list(range(300))
        assert evs[-1] == ("done", {"uri": "tls"})
        s.close()
    finally:
        d.close()
