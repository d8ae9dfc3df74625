"""Load generator child: speaks RESP over loopback to the program's wire
server and never imports jax.

The parent starts one child per role (`writers`, `readers`) with a JSON job
on stdin, reads `ready` once every connection is open, and sends the start
(`t_start`, `drain_s`, on the shared CLOCK_MONOTONIC). Load runs from then
on; once the warm-up has settled the parent sends the window (`t0`,
`t_end`), and the child stops sending at `t_end`, waits up to `drain_s`
for the replies due, and writes one length-prefixed `.npz` of per-command
times to stdout.

Writers run a closed loop: each connection keeps `pipeline` commands in
flight, as an application server's connection pool does (memtier_benchmark,
redis-benchmark -P). Readers run an open loop: read i is due at
t_start + i / rate on connection i % connections, whatever the replies do,
and is timed from when it was due.

With `cluster` in the job, each connection acts as a Redis Cluster client
(perfbench/slots.py): a command's frames go to the nodes that own their
keys, and the command completes at its last reply.
"""

from __future__ import annotations

import collections
import io
import json
import os
import selectors
import socket
import struct
import sys
import time

import numpy as np

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
        __file__))))

from perfbench import resp, slots, traffic  # noqa: E402

BATCH = 256  # commands made per connection at a time


class _Stdin:
    """Lines from the parent on fd 0, read without Python's buffering so
    the selector sees every line that arrives."""

    def __init__(self):
        self.buf = b""

    def fileno(self) -> int:
        return 0

    def _take(self):
        line, _, self.buf = self.buf.partition(b"\n")
        return json.loads(line)

    def readline(self):
        while b"\n" not in self.buf:
            got = os.read(0, 1 << 16)
            if not got:
                raise EOFError("the parent closed stdin")
            self.buf += got
        return self._take()


STDIN = _Stdin()


class _Node:
    """One socket to one server: the bytes not yet sent, the reply parser,
    and the frames sent on it that await replies, in order."""

    def __init__(self, conn: int, addr, sel, sock=None):
        self.conn = conn
        self.sock = sock or socket.create_connection(addr)
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self.sock.setblocking(False)
        self.out = bytearray()
        self.parser = resp.Parser()
        self.waiting = collections.deque()  # (command, frame position)
        self.sel = sel
        sel.register(self.sock, selectors.EVENT_READ, self)

    def flush(self):
        if self.out:
            try:
                n = self.sock.send(self.out)
            except BlockingIOError:
                n = 0
            del self.out[:n]
        want = selectors.EVENT_READ | (selectors.EVENT_WRITE if self.out
                                       else 0)
        self.sel.modify(self.sock, want, self)

    def done(self, data: bytes):
        """The commands whose last reply is in `data`."""
        out = []
        for reply in self.parser.feed(data):
            cmd, pos = self.waiting.popleft()
            cmd.replies[pos] = reply
            cmd.left -= 1
            if not cmd.left:
                out.append(cmd)
        return out


class _Cmd:
    """One command in flight on a logical connection; its frames' replies
    are kept in frame order."""

    __slots__ = ("conn", "j", "t_send", "i", "due", "left", "replies")

    def __init__(self, conn: "_Conn", j: int, i: int = 0, due: float = 0.0):
        self.conn, self.j, self.i, self.due = conn, j, i, due
        self.t_send = time.monotonic()


class _Conn:
    """One logical connection: its commands, made in batches, and one
    socket per node. Without a cluster that is one socket to the server;
    in a cluster the connection reads the slot map once (CLUSTER SLOTS)
    and sends each frame to the node that owns its key's slot. No
    redirect is followed: -MOVED and -ASK are error replies."""

    def __init__(self, idx: int, host: str, port: int, cluster: bool, sel):
        self.idx = idx
        self.made = collections.deque()  # (j, [(node, frame bytes)])
        self.next_j = 0
        self.slot_map = None
        if not cluster:
            self.nodes = [_Node(idx, (host, port), sel)]
            return
        seed = socket.create_connection((host, port))
        try:
            self.slot_map = slots.SlotMap(resp.call(seed, resp.Parser(),
                                                    ["CLUSTER", "SLOTS"]))
        except BaseException:
            seed.close()
            raise
        self.nodes = [_Node(idx, a, sel, seed if a == (host, port) else None)
                      for a in self.slot_map.nodes]
        if (host, port) not in self.slot_map.nodes:
            seed.close()

    def node_of(self, args) -> int:
        return 0 if self.slot_map is None else self.slot_map.node_of(
            slots.command_key(args))

    def send(self, cmd: _Cmd, frames) -> None:
        """Queue each frame on its node's socket, then send."""
        cmd.left = len(frames)
        cmd.replies = [None] * len(frames)
        touched = []
        for pos, (n, data) in enumerate(frames):
            node = self.nodes[n]
            node.out += data
            node.waiting.append((cmd, pos))
            if node not in touched:
                touched.append(node)
        for node in touched:
            node.flush()

    def close(self) -> None:
        for node in self.nodes:
            node.sock.close()


ERRORS: dict = {}  # error reply -> how many (first few kinds)


def _value(reply):
    """(value, failed): an integer, OK (0), or an array of integers (a
    tuple) is an answered reply; an error or a nil reply, an array that
    holds one, or anything else is a failed command."""
    if isinstance(reply, int):
        return reply, False
    if reply == "OK":
        return 0, False
    if isinstance(reply, list):
        bad = [x for x in reply if not isinstance(x, int)]
        if not bad:
            return tuple(reply), False
        reply = bad[0]
    what = str(reply) if isinstance(reply, resp.RespError) else \
        f"not an integer: {reply!r}"[:80]
    if what in ERRORS or len(ERRORS) < 5:
        ERRORS[what] = ERRORS.get(what, 0) + 1
    return 0, True


def _refill(role: traffic.Role, conn: _Conn) -> None:
    js = np.arange(conn.next_j, conn.next_j + BATCH)
    for j, cmds in zip(js, role.make(conn.idx, js)):
        conn.made.append((int(j), [(conn.node_of(a), resp.encode(a))
                                   for a in cmds]))
    conn.next_j += BATCH


def _encoded(role: traffic.Role, conn: _Conn):
    """Next command of this connection: (j, [(node, frame bytes), ...])."""
    if not conn.made:
        _refill(role, conn)
    return conn.made.popleft()


def _window(clock) -> None:
    """The parent's window line: sets `t_end` in `clock`."""
    clock.update(STDIN.readline())


def _completed(sel, timeout: float, clock, who: str):
    """(command, time of its last reply) for each command completed by what
    the sockets hold after waiting up to `timeout`; the parent's window
    line is read as it comes."""
    for key, mask in sel.select(timeout=timeout):
        node = key.data
        if node is STDIN:
            _window(clock)
            continue
        if mask & selectors.EVENT_WRITE:
            node.flush()
        if not mask & selectors.EVENT_READ:
            continue
        data = node.sock.recv(1 << 20)
        if not data:
            raise ConnectionError(f"{who} {node.conn}: server closed")
        now = time.monotonic()
        for cmd in node.done(data):
            yield cmd, now


def run_writers(role, conns, sel, clock, rec):
    depth = int(role.spec["pipeline"])

    def send(c):
        j, frames = _encoded(role, c)
        c.send(_Cmd(c, j), frames)

    _sleep_until(clock["t_start"])
    for c in conns:
        for _ in range(depth):
            send(c)
    inflight = depth * len(conns)
    while inflight and ("t_end" not in clock or time.monotonic()
                        < clock["t_end"] + clock["drain_s"]):
        for cmd, now in _completed(sel, 0.05, clock, "writer"):
            got_vals = [_value(x) for x in cmd.replies]
            v, err = got_vals[-1][0], any(e for _, e in got_vals)
            rec.append((cmd.conn.idx, cmd.j, cmd.t_send, now, v, err))
            inflight -= 1
            if now < clock.get("t_end", np.inf):
                send(cmd.conn)
                inflight += 1
    return {"unanswered": inflight}


def run_readers(role, conns, sel, clock, rec):
    rate = float(role.spec["rate"])
    t0 = clock["t_start"]
    n_due = np.inf  # until the window's end is known
    i = 0
    pending = 0
    while (i < n_due or pending) and ("t_end" not in clock or time.monotonic()
                                      < clock["t_end"] + clock["drain_s"]):
        if "t_end" in clock:
            n_due = int(np.ceil((clock["t_end"] - t0) * rate))
        now = time.monotonic()
        while i < n_due and t0 + i / rate <= now:
            c = conns[i % len(conns)]
            j, frames = _encoded(role, c)
            c.send(_Cmd(c, j, i, t0 + i / rate), frames)
            i += 1
            pending += 1
        timeout = (t0 + i / rate - time.monotonic()) if i < n_due else 0.05
        for cmd, got in _completed(sel, max(0.0, min(timeout, 0.05)), clock,
                                   "reader"):
            got_vals = [_value(x) for x in cmd.replies]
            rec.append((cmd.i, cmd.conn.idx, cmd.j, cmd.due, cmd.t_send, got,
                        any(e for _, e in got_vals),
                        [v for v, _ in got_vals]))
            pending -= 1
    return {"unanswered": pending, "due": int(n_due)}


def _sleep_until(t: float) -> None:
    while True:
        left = t - time.monotonic()
        if left <= 0:
            return
        time.sleep(min(left, 0.05))


def _slots(values) -> np.ndarray:
    """Replies as int64 slots: [n] where every reply is an integer; else
    [n, width], each array reply zero-padded to the longest (an integer
    fills one slot)."""
    if all(isinstance(v, int) for v in values):
        return np.asarray(values, np.int64)
    values = [v if isinstance(v, tuple) else (v,) for v in values]
    out = np.zeros((len(values), max(map(len, values))), np.int64)
    for row, v in zip(out, values):
        row[:len(v)] = v
    return out


def _pack(role_name: str, rec, info) -> bytes:
    """The records as one .npz. A read's replies fill `values`, one slot
    a command; where some command answers an array, each command gets
    the width of its longest reply, and `widths` says how many."""
    buf = io.BytesIO()
    if role_name == "writers":
        cols = list(zip(*rec)) if rec else [[]] * 6
        np.savez(buf, conn=np.asarray(cols[0], np.int64),
                 j=np.asarray(cols[1], np.int64),
                 t_send=np.asarray(cols[2], np.float64),
                 t_reply=np.asarray(cols[3], np.float64),
                 value=_slots(cols[4]),
                 err=np.asarray(cols[5], bool),
                 info=np.asarray(json.dumps(info)))
    else:
        cols = list(zip(*rec)) if rec else [[]] * 8
        per_cmd = [_slots(c) for c in zip(*cols[7])]
        extra = {}
        if any(s.ndim > 1 for s in per_cmd):
            per_cmd = [s.reshape(len(rec), -1) for s in per_cmd]
            extra["widths"] = np.asarray([s.shape[1] for s in per_cmd],
                                         np.int64)
            values = np.concatenate(per_cmd, axis=1)
        else:
            values = np.asarray(cols[7], np.int64).reshape(len(rec), -1)
        np.savez(buf, i=np.asarray(cols[0], np.int64),
                 conn=np.asarray(cols[1], np.int64),
                 j=np.asarray(cols[2], np.int64),
                 due=np.asarray(cols[3], np.float64),
                 t_send=np.asarray(cols[4], np.float64),
                 t_reply=np.asarray(cols[5], np.float64),
                 err=np.asarray(cols[6], bool),
                 values=values, **extra,
                 info=np.asarray(json.dumps(info)))
    return buf.getvalue()


def main() -> int:
    job = STDIN.readline()
    role = traffic.Role(job["role"], job["spec"], job["space"], job["seed"])
    sel = selectors.DefaultSelector()
    conns = [_Conn(i, job["host"], job["port"], job["cluster"],
                   sel) for i in range(role.connections)]
    for c in conns:  # make the first batch before the clock starts
        _refill(role, c)
    out = sys.stdout.buffer
    out.write(b"ready\n")
    out.flush()
    clock = STDIN.readline()
    sel.register(STDIN, selectors.EVENT_READ, STDIN)
    rec = []
    run = run_writers if role.name == "writers" else run_readers
    info = run(role, conns, sel, clock, rec)
    for c in conns:
        c.close()
    assert "jax" not in sys.modules, "the load generator imported jax"
    info["jax_imported"] = False
    info["errors"] = ERRORS
    blob = _pack(role.name, rec, info)
    out.write(struct.pack("<Q", len(blob)) + blob)
    out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
