"""Dense HyperLogLogs (`"type": "hll"`): one p=14 HLL a key, filled with
`fill.elements` ids drawn uniformly from `fill.of`. PFADD writes, PFCOUNT
reads, and the state is the 16,384 registers (perfbench/ref/hll.py)."""

from __future__ import annotations

import numpy as np

from perfbench import traffic
from perfbench.ref import hll as rhll

#: the program's op kinds that answer a PFCOUNT
READ_KINDS = frozenset({"hll_count", "hll_count_with"})

FILL_BATCH = 2048  # keys a facade batch


def _fill_ids(entry, key_index, seed: int, space) -> np.ndarray:
    f = entry["fill"]
    return traffic.fill_elements(seed, np.asarray(key_index),
                                 int(f["elements"]),
                                 traffic.value(f["of"], space))


def fill(system, keys, seed: int) -> None:
    """PFADD through the facade in batches of FILL_BATCH keys; in a
    cluster the facade's router sends each key to its shard."""
    for lo in range(0, len(keys), FILL_BATCH):
        part = keys[lo:lo + FILL_BATCH]
        elems = _fill_ids(part[0][1], [i for _, _, i in part], seed,
                          system.space)
        b = system.client.create_batch()
        for (k, _, _), ids in zip(part, elems):
            b.get_hyper_log_log(k).add_ints_async(ids)
        b.execute()


def base(entry, key_index: int, seed: int, space) -> np.ndarray:
    return rhll.hll_registers(_fill_ids(entry, [key_index], seed, space)[0])


def readback(key: str) -> list:
    return ["PFCOUNT", key]


class PFAdd:
    """`PFADD key id...`: each id raises its register to its rank."""

    def payload(self, args):
        return rhll.bucket_rank(np.asarray(args[2], np.uint64))

    def apply(self, state: np.ndarray, payload) -> None:
        b, r = payload
        np.maximum.at(state, b, r)

    def wrong(self, h, replies, control):
        """(wrong, checked) replies of one key's history `h`: a reply of 1
        is wrong where the writes acknowledged before it was sent already
        hold every (bucket, rank) it adds, since then no register can
        change wherever it is ordered. The control (`lag_s` > 0) replaces
        the replies by a reference's that answers from state that much
        older."""
        lag_s = control.get("lag_s", 0.0)
        if not len(h.idx):
            return 0, 0
        st = h.base().copy()
        stale = st.copy()
        acked = np.argsort(h.t_reply, kind="stable")
        acked = acked[h.ok[acked]]
        wrong = checked = w = v = 0
        for q in np.argsort(h.t_send, kind="stable"):
            while w < len(acked) and h.t_reply[acked[w]] < h.t_send[q]:
                self.apply(st, h.pay[acked[w]])
                w += 1
            if not h.ok[q]:
                continue
            checked += 1
            b, r = h.pay[q]
            reply = replies[q]
            if lag_s:
                while v < len(acked) and (h.t_reply[acked[v]]
                                          < h.t_send[q] - lag_s):
                    self.apply(stale, h.pay[acked[v]])
                    v += 1
                reply = int(np.any(r > stale[b]))
            if reply == 1 and not np.any(r > st[b]):
                wrong += 1
        return wrong, checked


class PFCount:
    """`PFCOUNT key...`: the estimate of the registers' union, which the
    program rounds to an integer."""

    rounds = True

    def keys_read(self, args):
        return list(args[1:])

    def answer(self, args, states):
        return rhll.estimate(np.maximum.reduce(states) if len(states) > 1
                             else states[0])


WRITES = {"PFADD": PFAdd()}
READS = {"PFCOUNT": PFCount()}
