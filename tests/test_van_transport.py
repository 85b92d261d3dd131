"""The van's transport, derived per connection (ISSUE 38): a peer whose
resolved dial address is on this host is offered a shm ring and answers;
a refused, unanswered or locally failed offer leaves the connection on
TCP. Real processes on loopback and raw-socket peers, each with a time
limit of its own. The heavier cases of both transports (MB-scale traffic,
tiny rings, stripes, pacing, the sanitizers) are tests/test_ps_core.py."""

import contextlib
import mmap
import os
import socket
import struct
import threading
import time

import pytest

from tests.ps_utils import TCP, assert_transport, free_port, spawn_role, \
    spawn_worker, topology_env, van_conns

WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "_ps_worker.py")


def _run_fleet(env, mode, server_env=None):
    """Scheduler, one server, two workers in `mode`: their outputs in that
    order, every exit code 0, within 90 s."""
    procs = [spawn_role("scheduler", env),
             spawn_role("server", {**env, **(server_env or {})}),
             spawn_worker(WORKER, env, 0, mode),
             spawn_worker(WORKER, env, 1, mode)]
    try:
        outs = [p.communicate(timeout=90)[0] for p in procs]
        assert [p.returncode for p in procs] == [0] * 4, outs
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.communicate()
    return outs


def _loopback_env(extra=None):
    """A 2-worker, 1-server fleet's environment at DEBUG, with no
    transport variable but what `extra` says (whatever the caller's shell
    has)."""
    env = topology_env(2, 1, free_port(), {"BYTEPS_LOG_LEVEL": "DEBUG"})
    env.pop("BYTEPS_VAN_TYPE", None)
    env.update(extra or {})
    return env


@pytest.mark.parametrize("extra,want", [
    ({}, "shm"),
    ({"BYTEPS_VAN_TYPE": "shm"}, "shm"),
    (TCP, "tcp"),
], ids=["default", "shm", "tcp"])
def test_van_transport_is_derived(extra, want):
    """With no transport variable set a loopback fleet's connections are
    on shm rings: the van derives it from the dialled address.
    BYTEPS_VAN_TYPE=shm means that default; `tcp` forces sockets. Read
    from the counters and the van's DEBUG line, so a silent fallback (or
    a silent ring) fails."""
    outs = _run_fleet(_loopback_env(extra), "basic")
    assert_transport(outs[2:], want, dialled=2)  # scheduler + server
    # The acceptors' side of the same connections: the server took its two
    # workers' offers, the scheduler three (and with `tcp` got none).
    for out, n in zip(outs[:2], (3, 2)):
        assert out.count("accepted shm ring") == (n if want == "shm" else 0)
        assert " WARN " not in out, out[-2000:]


# --- the ring offer refused, both ways (raw-socket peers) -------------------

_HEADER_FMT = "<hHiqiiqiiqqq"  # MsgHeader, common.h (64 bytes, packed)
_CMD_HEARTBEAT, _CMD_SHM_HELLO, _CMD_HEARTBEAT_ACK, _CMD_SHM_ACK = \
    11, 16, 25, 38


def _frame(cmd, payload=b"", arg0=0):
    head = struct.pack(_HEADER_FMT, cmd, 0, -1, 0, -1, 0, len(payload), 0,
                       0, arg0, 0, 0)
    return struct.pack("<Q", len(head) + len(payload)) + head + payload


def _read_frame(sock):
    """One framed message off a raw socket: (cmd, arg0, payload)."""
    def exact(n):
        buf = b""
        while len(buf) < n:
            chunk = sock.recv(n - len(buf))
            if not chunk:
                raise ConnectionError("peer closed")
            buf += chunk
        return buf
    total = struct.unpack("<Q", exact(8))[0]
    body = exact(total)
    f = struct.unpack_from(_HEADER_FMT, body, 0)
    return f[0], f[9], body[64:]


@contextlib.contextmanager
def _scheduler_and_socket(log):
    """A real scheduler and a raw socket dialled to its van; the
    scheduler is killed at exit and its output appended to `log`."""
    port = free_port()
    sched = spawn_role("scheduler", topology_env(1, 1, port))
    try:
        deadline, c = time.time() + 20, None
        while c is None:
            try:
                c = socket.create_connection(("127.0.0.1", port), timeout=10)
            except OSError:
                assert time.time() < deadline
                time.sleep(0.05)
        with c:
            yield c
    finally:
        sched.kill()
        log.append(sched.communicate()[0])


@pytest.mark.parametrize("case", ["no_such_segment", "bad_capacity",
                                  "short_segment"])
def test_van_refuses_a_ring_it_cannot_map(case):
    """Acceptor side of a refusal: a hello naming a segment this van
    cannot map (absent, a capacity no healthy peer sends, smaller than it
    says) is ANSWERED — CMD_SHM_ACK, arg0 0 — and the connection goes on
    serving over its socket: the heartbeat sent next is echoed there. One
    WARNING in the acceptor's log names the reason. (Before ISSUE 38 the
    acceptor dropped the connection, fail-stop.)"""
    name, cap = f"/bpsvan_test_{os.getpid()}_{case}", 65536
    seg = "/dev/shm" + name
    if case == "bad_capacity":
        cap = 3
    elif case == "short_segment":
        with open(seg, "wb") as f:
            f.write(b"\0" * 4096)
    log = []
    try:
        with _scheduler_and_socket(log) as c:
            c.sendall(_frame(_CMD_SHM_HELLO, name.encode(), arg0=cap))
            assert _read_frame(c) == (_CMD_SHM_ACK, 0, b"")
            c.sendall(_frame(_CMD_HEARTBEAT, arg0=12345))
            cmd, arg0, _ = _read_frame(c)
            assert (cmd, arg0) == (_CMD_HEARTBEAT_ACK, 12345)
    finally:
        if os.path.exists(seg):
            os.unlink(seg)
    assert log[0].count("shm ring offer refused") == 1, log[0][-2000:]


def test_van_drains_the_ring_before_it_reports_the_peer_lost():
    """A peer writes its last frames into the ring and closes: the EOF
    travels on the socket BESIDE those frames, not behind them as on one
    TCP stream, so the van must deliver what is in the ring before it
    reports the loss and drops the connection (a scheduler's SHUTDOWN
    broadcast ahead of its exit is such a frame: reported lost first, its
    servers took a clean end for a failure and exited non-zero). A
    raw-socket connector offers a real segment, puts one heartbeat in the
    ring and closes at once; the van's echo must land in the return ring
    of the mapping this test still holds."""
    cap, hdr = 65536, 448  # sizeof(ShmHeader): 8 + pad to 64 + 2 x ShmDir
    tail0, tail1 = 64, 64 + 192  # ShmDir::tail of dir[0] / dir[1]
    name = f"/bpsvan_test_{os.getpid()}_drain"
    seg = "/dev/shm" + name
    with open(seg, "w+b") as f:
        f.truncate(hdr + 2 * cap)
        mm = mmap.mmap(f.fileno(), hdr + 2 * cap)
    struct.pack_into("<II", mm, 0, 0x62707331, cap)  # kShmMagic
    try:
        with _scheduler_and_socket([]) as c:
            c.sendall(_frame(_CMD_SHM_HELLO, name.encode(), arg0=cap))
            assert _read_frame(c) == (_CMD_SHM_ACK, 1, b"")
            beat = _frame(_CMD_HEARTBEAT, arg0=777)
            mm[hdr:hdr + len(beat)] = beat
            struct.pack_into("<I", mm, tail0, len(beat))  # publish, no wake
            c.close()
            deadline = time.time() + 10
            while struct.unpack_from("<I", mm, tail1)[0] < 72:
                assert time.time() < deadline, "no echo in the return ring"
                time.sleep(0.01)
        echo = bytes(mm[hdr + cap:hdr + cap + 72])
        assert struct.unpack_from("<Q", echo)[0] == 64
        f = struct.unpack_from(_HEADER_FMT, echo, 8)
        assert (f[0], f[9]) == (_CMD_HEARTBEAT_ACK, 777)
    finally:
        if os.path.exists(seg):
            os.unlink(seg)


class _RefusingRelay:
    """A fake acceptor in front of a real server: answers a ring offer
    itself (`refuse`) or swallows it (`silent`), and forwards everything
    else byte for byte — a port-forward to 127.0.0.1, whose far end does
    not share this process's /dev/shm."""

    def __init__(self, upstream_port, behaviour):
        self.up, self.behaviour, self.hellos = upstream_port, behaviour, 0
        self.srv = socket.socket()
        self.srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self.srv.bind(("127.0.0.1", 0))
        self.srv.listen(16)
        self.port = self.srv.getsockname()[1]
        threading.Thread(target=self._accept, daemon=True).start()

    def _accept(self):
        while True:
            try:
                c, _ = self.srv.accept()
            except OSError:
                return
            threading.Thread(target=self._conn, args=(c,),
                             daemon=True).start()

    def _conn(self, c):
        try:
            first = c.recv(72, socket.MSG_WAITALL)  # length + header
            if len(first) == 72 and struct.unpack_from(
                    "<h", first, 8)[0] == _CMD_SHM_HELLO:
                self.hellos += 1
                c.recv(struct.unpack_from("<Q", first)[0] - 64,
                       socket.MSG_WAITALL)  # the segment's name
                if self.behaviour == "silent":
                    c.recv(1)  # until the connector gives up and closes
                    return
                c.sendall(_frame(_CMD_SHM_ACK, arg0=0))
                first = b""
            up = socket.create_connection(("127.0.0.1", self.up), timeout=20)
            up.settimeout(None)
            up.sendall(first)

            def pump(a, b):
                try:
                    while True:
                        d = a.recv(1 << 16)
                        if not d:
                            break
                        b.sendall(d)
                except OSError:
                    pass
                for x in (a, b):
                    try:
                        x.shutdown(socket.SHUT_RDWR)
                    except OSError:
                        pass
            threading.Thread(target=pump, args=(up, c), daemon=True).start()
            pump(c, up)
        except OSError:
            pass
        finally:
            c.close()


@pytest.mark.parametrize("behaviour,warning", [
    ("refuse", "the peer refused the shm ring offer"),
    ("silent", "the peer gave no answer to the shm ring offer"),
], ids=["refuse", "silent"])
def test_van_ring_offer_refused_falls_back_to_tcp(behaviour, warning):
    """Connector side of a refusal: the server is reached through a relay
    on 127.0.0.1 (the address is local, the far end's /dev/shm is not
    ours) that refuses the workers' ring offer, or never answers it. The
    connection stays on TCP — after `silent`, a fresh one dialled without
    an offer — push/pull over it is exact, and each worker shows one
    fallback in its counters and one WARNING; its scheduler link is on a
    ring all the same (per connection, not per process)."""
    listen = free_port()
    relay = _RefusingRelay(listen, behaviour)  # bound before the next pick
    try:
        outs = _run_fleet(_loopback_env(), "multipart", server_env={
            "BYTEPS_LISTEN_PORT": str(listen),
            "BYTEPS_ADVERTISED_PORT": str(relay.port)})
    finally:
        relay.srv.close()
    # Each worker offered through the relay once while it worked; at the
    # fleet's end a worker may see the server go first and dial it again,
    # so what counts is read up to the worker's own `van_conns` line.
    assert relay.hellos >= 2
    for o in outs[2:]:
        assert van_conns(o) == {"shm": 1, "tcp": 1, "fallback": 1}, \
            o[-2000:]
        worked = o[:o.index("van_conns ")]
        assert worked.count(" WARN ") == 1 and warning in worked, o[-2000:]
        assert "multipart OK" in o
