"""The embedded broker's XREAD over many streams (serving/resp.py), with
Redis's semantics: ``XREAD [COUNT c] [BLOCK ms] STREAMS k1 .. kn id1 ..
idn`` returns ``[key, entries]`` for the keys that have entries past
their id, COUNT a key; a blocked read wakes on an add to any key it
names and on nothing else; ``$`` is each stream's own last id.  The
single-key form answers byte for byte as it did, and a connection's
replies to a pipeline leave in one write."""

import socket
import threading
import time

import pytest

from analytics_zoo_tpu.serving.resp import (RedisError, RespClient,
                                            RespServer, _encode_command,
                                            encode)


@pytest.fixture
def broker():
    srv = RespServer(port=0).start()
    clients = []

    def client():
        c = RespClient("127.0.0.1", srv.port)
        clients.append(c)
        return c

    try:
        yield srv, client
    finally:
        for c in clients:
            c.close()
        srv.stop()


def _ids(reply):
    return {k: [e[0] for e in entries] for k, entries in reply}


def _blocked_read(client, *args):
    """Run a blocking XREAD on a thread: (thread, box for its reply)."""
    box = []
    t = threading.Thread(target=lambda: box.append(
        client.execute("XREAD", *args)))
    t.start()
    return t, box


def test_only_the_keys_with_new_entries_come_back(broker):
    srv, client = broker
    c = client()
    a1 = c.execute("XADD", "a", "*", "f", "1")
    b1 = c.execute("XADD", "b", "*", "f", "1")
    c1 = c.execute("XADD", "c", "*", "f", "1")
    c2 = c.execute("XADD", "c", "*", "f", "2")
    got = c.execute("XREAD", "STREAMS", "a", "b", "c", "nokey",
                    a1, "0-0", c1, "0-0")
    # in the order the keys were named; a and the missing key left out
    assert [k for k, _ in got] == [b"b", b"c"]
    assert _ids(got) == {b"b": [b1], b"c": [c2]}
    assert got[1][1] == [[c2, [b"f", b"2"]]]
    assert c.execute("XREAD", "STREAMS", "a", "b", a1, b1) is None


def test_count_applies_to_each_key(broker):
    srv, client = broker
    c = client()
    ids = {k: [c.execute("XADD", k, "*", "i", str(i)) for i in range(n)]
           for k, n in (("a", 5), ("b", 3), ("c", 1))}
    got = c.execute("XREAD", "COUNT", "2", "STREAMS", "a", "b", "c",
                    "0-0", "0-0", "0-0")
    assert _ids(got) == {b"a": ids["a"][:2], b"b": ids["b"][:2],
                         b"c": ids["c"]}
    got = c.execute("XREAD", "COUNT", "2", "STREAMS", "a", "b",
                    ids["a"][1], ids["b"][1])
    assert _ids(got) == {b"a": ids["a"][2:4], b"b": ids["b"][2:]}


def test_a_block_wakes_on_any_named_key_and_not_on_another(broker):
    srv, client = broker
    reader, writer = client(), client()
    t, box = _blocked_read(reader, "BLOCK", "10000", "STREAMS", "x", "y",
                           "$", "$")
    time.sleep(0.2)
    writer.execute("XADD", "z", "*", "f", "unnamed")
    time.sleep(0.3)
    assert t.is_alive() and not box     # an unnamed key wakes nothing
    t0 = time.monotonic()
    eid = writer.execute("XADD", "y", "*", "f", "named")
    t.join(timeout=5)
    assert not t.is_alive()
    assert time.monotonic() - t0 < 2.0
    assert box == [[[b"y", [[eid, [b"f", b"named"]]]]]]
    # the read left no waiter behind on any of its streams
    assert all(not srv.streams[k].waiters for k in (b"x", b"y", b"z"))


def test_the_block_window_runs_out_with_nil(broker):
    srv, client = broker
    c = client()
    c.execute("XADD", "a", "*", "f", "1")
    t0 = time.monotonic()
    assert c.execute("XREAD", "BLOCK", "250", "STREAMS", "a", "b",
                     "$", "0-0") is None
    assert time.monotonic() - t0 >= 0.24
    assert not srv.streams[b"a"].waiters


def test_dollar_is_each_streams_own_last_id(broker):
    srv, client = broker
    reader, writer = client(), client()
    writer.execute("XADD", "a", "*", "f", "old")
    b1 = writer.execute("XADD", "b", "*", "f", "old")
    # `$` on a hides a's entry; an explicit id on b reads b's
    got = reader.execute("XREAD", "BLOCK", "1000", "STREAMS", "a", "b",
                         "$", "0-0")
    assert _ids(got) == {b"b": [b1]}
    t, box = _blocked_read(reader, "BLOCK", "10000", "STREAMS", "a", "b",
                           "$", "$")
    time.sleep(0.2)
    a2 = writer.execute("XADD", "a", "*", "f", "new")
    t.join(timeout=5)
    assert not t.is_alive() and _ids(box[0]) == {b"a": [a2]}


def test_an_unbalanced_list_of_streams_is_an_error(broker):
    srv, client = broker
    c = client()
    with pytest.raises(RedisError, match="Unbalanced"):
        c.execute("XREAD", "STREAMS", "a", "b", "0-0")
    assert c.execute("PING") == "PONG"      # the connection is in step


def _raw(port, payload: bytes, want: int, timeout=5.0) -> bytes:
    """Send ``payload`` on a raw socket; read until ``want`` bytes."""
    s = socket.create_connection(("127.0.0.1", port), timeout=timeout)
    try:
        s.sendall(payload)
        buf = b""
        while len(buf) < want:
            chunk = s.recv(65536)
            assert chunk, buf
            buf += chunk
        return buf
    finally:
        s.close()


def test_the_single_key_form_answers_byte_for_byte(broker):
    srv, client = broker
    c = client()
    e1 = c.execute("XADD", "s", "*", "t", "5", "s", "123")
    e2 = c.execute("XADD", "s", "*", "done", "1")
    cases = [
        (("XREAD", "COUNT", 256, "BLOCK", 100, "STREAMS", "s", "0-0"),
         encode([[b"s", [[e1, [b"t", b"5", b"s", b"123"]],
                         [e2, [b"done", b"1"]]]]])),
        (("XREAD", "COUNT", 1, "STREAMS", "s", e1),
         encode([[b"s", [[e2, [b"done", b"1"]]]]])),
        (("XREAD", "BLOCK", 50, "STREAMS", "s", "$"), b"$-1\r\n"),
        (("XREAD", "STREAMS", "s", e2), b"$-1\r\n"),
    ]
    for cmd, wire in cases:
        assert _raw(srv.port, _encode_command(cmd), len(wire)) == wire


def test_a_pipelines_replies_leave_in_one_write(broker):
    """The connection thread runs every command it holds before it
    writes, so 64 replies are one write; a blocking read in a pipeline
    first answers what came before it."""
    srv, client = broker
    pings = b"".join(_encode_command(("PING",)) for _ in range(64))
    s = socket.create_connection(("127.0.0.1", srv.port), timeout=5)
    try:
        s.sendall(pings)
        assert s.recv(65536) == b"+PONG\r\n" * 64
        s.sendall(_encode_command(("PING",)) + _encode_command(
            ("XREAD", "BLOCK", 5000, "STREAMS", "q", "$")))
        assert s.recv(65536) == b"+PONG\r\n"    # the read still blocks
        eid = client().execute("XADD", "q", "*", "f", "v")
        want = encode([[b"q", [[eid, [b"f", b"v"]]]]])
        buf = b""
        while len(buf) < len(want):
            buf += s.recv(65536)
        assert buf == want
    finally:
        s.close()
