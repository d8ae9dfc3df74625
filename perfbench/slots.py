"""Redis Cluster key slots, the benchmark's own copy (standard library
only, so the load generator can use it and a PR to the program cannot move
it): CRC16-XMODEM of a key's hashtag mod 16384, the key a command is
routed by, and the slot map that a `CLUSTER SLOTS` reply gives, as
redis-py's `RedisCluster` and memtier_benchmark's `--cluster-mode` read
it."""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

SLOTS = 16384


def _crc_table() -> Tuple[int, ...]:
    out = []
    for i in range(256):
        c = i << 8
        for _ in range(8):
            c = (c << 1) ^ 0x1021 if c & 0x8000 else c << 1
        out.append(c & 0xFFFF)
    return tuple(out)


_TABLE = _crc_table()


def crc16(data: bytes) -> int:
    """CRC16-XMODEM (polynomial 0x1021, initial 0), as the Redis Cluster
    specification defines it."""
    crc = 0
    for b in data:
        crc = ((crc << 8) & 0xFFFF) ^ _TABLE[((crc >> 8) ^ b) & 0xFF]
    return crc


def hashtag(key: bytes) -> bytes:
    """What the slot hashes: the bytes between the first `{` and the first
    `}` after it, where that is not empty; else the whole key."""
    start = key.find(b"{")
    if start >= 0:
        end = key.find(b"}", start + 1)
        if end > start + 1:
            return key[start + 1:end]
    return key


def key_slot(key) -> int:
    if isinstance(key, str):
        key = key.encode()
    return crc16(hashtag(key)) % SLOTS


def command_key(args: Sequence) -> Optional[str]:
    """The key a cluster client routes a command by: its first key, which
    is argument 1, or 2 for BITOP (Redis's command table: `BITOP op dest
    src...`); None for a command without one."""
    at = 2 if str(args[0]).upper() == "BITOP" else 1
    return args[at] if len(args) > at else None


def _text(x) -> str:
    return x.decode() if isinstance(x, (bytes, bytearray)) else str(x)


class SlotMap:
    """Which node serves each slot, from a `CLUSTER SLOTS` reply: one entry
    `[start, end, [host, port, id], replica...]` per range, inclusive.
    Nodes are numbered in the order the reply first names them."""

    def __init__(self, reply: List):
        if not isinstance(reply, list) or not reply:
            raise ValueError(f"CLUSTER SLOTS gave no slot ranges: {reply!r}")
        self.nodes: List[Tuple[str, int]] = []
        index: Dict[Tuple[str, int], int] = {}
        self.owner = [-1] * SLOTS
        for start, end, master, *_ in reply:
            addr = (_text(master[0]) or "127.0.0.1", int(master[1]))
            n = index.setdefault(addr, len(self.nodes))
            if n == len(self.nodes):
                self.nodes.append(addr)
            self.owner[int(start):int(end) + 1] = \
                [n] * (int(end) + 1 - int(start))
        missing = self.owner.count(-1)
        if missing:
            raise ValueError(f"CLUSTER SLOTS leaves {missing} slots unserved")

    def node_of(self, key) -> int:
        """The node of `key`'s slot; node 0 for a command without a key."""
        return 0 if key is None else self.owner[key_slot(key)]
