"""The benchmark's own RESP2 encoder and reply parser (numpy only), so a
PR to the program's client cannot move the yardstick."""

from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np


class RespError(str):
    """An error reply (`-ERR ...`)."""


def encode(args) -> bytes:
    """One command frame. An argument is str, bytes, int, or a uint64
    array that expands to that many 8-byte little-endian bulk strings."""
    parts = []
    n = 0
    for a in args:
        if isinstance(a, np.ndarray):
            ids = np.ascontiguousarray(a, "<u8")
            block = np.empty((ids.shape[0], 14), np.uint8)
            block[:, :4] = np.frombuffer(b"$8\r\n", np.uint8)
            block[:, 4:12] = ids.view(np.uint8).reshape(-1, 8)
            block[:, 12:] = np.frombuffer(b"\r\n", np.uint8)
            parts.append(block.tobytes())
            n += ids.shape[0]
            continue
        if isinstance(a, str):
            a = a.encode()
        elif isinstance(a, int):
            a = str(a).encode()
        parts.append(b"$%d\r\n%s\r\n" % (len(a), a))
        n += 1
    return b"*%d\r\n" % n + b"".join(parts)


def call(sock, parser: "Parser", args):
    """Send one command on a blocking socket and return its reply."""
    sock.sendall(encode(args))
    while True:
        data = sock.recv(1 << 16)
        if not data:
            raise ConnectionError("the server closed the connection")
        got = parser.feed(data)
        if got:
            return got[0]


class Parser:
    """Incremental parser of replies: integers, simple strings, errors,
    bulk strings and arrays of those; a nil bulk string or nil array is
    None."""

    def __init__(self):
        self.buf = bytearray()
        self.pos = 0

    def feed(self, data: bytes) -> List[object]:
        self.buf += data
        out = []
        while True:
            item = self._one(self.pos)
            if item is None:
                break
            value, self.pos = item
            out.append(value)
        if self.pos > 65536:
            del self.buf[:self.pos]
            self.pos = 0
        return out

    def _line(self, pos: int) -> Optional[Tuple[bytes, int]]:
        end = self.buf.find(b"\r\n", pos)
        if end < 0:
            return None
        return bytes(self.buf[pos:end]), end + 2

    def _one(self, pos: int):
        if pos >= len(self.buf):
            return None
        line = self._line(pos + 1)
        if line is None:
            return None
        body, nxt = line
        tag = self.buf[pos:pos + 1]
        if tag == b":":
            return int(body), nxt
        if tag == b"+":
            return body.decode(), nxt
        if tag == b"-":
            return RespError(body.decode(errors="replace")), nxt
        if tag == b"$":
            n = int(body)
            if n < 0:
                return None, nxt
            if len(self.buf) < nxt + n + 2:
                return None
            return bytes(self.buf[nxt:nxt + n]), nxt + n + 2
        if tag == b"*":
            n = int(body)
            if n < 0:  # a nil array
                return None, nxt
            items = []
            for _ in range(n):
                item = self._one(nxt)
                if item is None:
                    return None
                value, nxt = item
                items.append(value)
            return items, nxt
        raise ValueError(f"bad RESP reply tag {bytes(tag)!r}")
