"""Bit sets (`"type": "bits"`): `bits` bits a key, packed as Redis stores
them (perfbench/ref/bits.py). The fill sets each bit with probability
`fill.density` by the program's bulk import, then the offsets in
`fill.set` by SETBIT. SETBIT 1 writes; BITOP, BITCOUNT read."""

from __future__ import annotations

import numpy as np

from perfbench import traffic
from perfbench.ref import bits as rbits

#: the program's op kinds that answer a bit-set read
READ_KINDS = frozenset({"bitset_op", "bitset_cardinality", "bitset_size",
                        "bitset_get"})


def fill(system, keys, seed: int) -> None:
    from redisson_tpu.store import ObjectType

    for k, e, i in keys:
        nbits = traffic.value(e["bits"], system.space)
        f = e["fill"]
        if "density" in f:
            cells = traffic.fill_cells(seed, i, nbits, float(f["density"]))
            system.dispatch.execute_sync(k, "bits_import",
                                         {"otype": ObjectType.BITSET,
                                          "array": cells,
                                          "meta": {"nbits": nbits}})
        if "set" in f:
            system.client.get_bit_set(k).set_bits(
                [traffic.value(x, system.space) for x in f["set"]])


def base(entry, key_index: int, seed: int, space) -> np.ndarray:
    nbits = traffic.value(entry["bits"], space)
    f = entry["fill"]
    if "density" in f:
        st = np.packbits(traffic.fill_cells(seed, key_index, nbits,
                                            float(f["density"])))
    else:
        st = rbits.make(nbits)
    if "set" in f:
        rbits.set_bits(st, [traffic.value(x, space) for x in f["set"]])
    return st


def readback(key: str) -> list:
    return ["BITCOUNT", key]


class SetBit:
    """`SETBIT key offset 1`, which replies with the bit's previous
    value."""

    def payload(self, args):
        if args[3] != "1":
            raise ValueError("the model replays SETBIT 1 only")
        return int(args[2])

    def apply(self, state: np.ndarray, payload) -> None:
        rbits.set_bits(state, [payload])

    def wrong(self, h, replies, control):
        """(wrong, checked) offsets of one key's history `h`: the
        acknowledged replies of one offset hold exactly one 0, or none if
        the fill had set the bit; an offset with a reply other than 0 or 1
        is wrong. The control (`drop_every` > 0) replaces the replies by a
        serial reference's that loses every n-th acknowledged write."""
        drop_every = control.get("drop_every", 0)
        ok = np.nonzero(h.ok)[0]
        ok = ok[np.argsort(h.t_reply[ok], kind="stable")]
        vals = np.asarray(replies)[ok].copy()
        offs = np.asarray([h.pay[n] for n in ok], np.int64)
        base = h.base()
        if drop_every:
            seen = {}
            for n, o in enumerate(offs):
                bit = seen.get(o)
                if bit is None:
                    bit = int(rbits.get_bits(base, [o])[0])
                vals[n] = bit
                if n % drop_every:
                    seen[o] = 1
        uniq, inv = np.unique(offs, return_inverse=True)
        if np.any((vals != 0) & (vals != 1)):
            return len(uniq), len(uniq)
        zeros = np.bincount(inv, weights=vals == 0, minlength=len(uniq))
        want = 1 - rbits.get_bits(base, uniq)
        return int(np.sum(zeros != want)), len(uniq)


class BitOp:
    """`BITOP op dest src...`: the destination is the read's own, made
    from the sources; the reply is its length in bytes."""

    rounds = False

    def keys_read(self, args):
        return list(args[3:])

    def makes(self, args) -> str:
        return args[2]

    def make(self, args, states) -> np.ndarray:
        return rbits.bitop(args[1], *states)

    def answer(self, args, states):
        return float(max(len(s) for s in states))


class BitCount:
    """`BITCOUNT key`: the bits set."""

    rounds = False

    def keys_read(self, args):
        return list(args[1:2])

    def answer(self, args, states):
        return float(rbits.bitcount(states[0]))


WRITES = {"SETBIT": SetBit()}
READS = {"BITOP": BitOp(), "BITCOUNT": BitCount()}
