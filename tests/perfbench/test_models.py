"""The command models of `correct`, pinned on fixed synthetic histories.

Each history is the load generator's records for one connection of
writers and one of readers, with no two commands in flight at once, so a
serial replay of the plain references (perfbench/ref) gives every right
reply. A case then shifts the read answers, or one write reply, or hands
the replies to the control (run.CONTROL), and holds the numbers compared
to values pinned on the harness as it was before the command models
moved into perfbench/state/."""

import io
import re
import subprocess
import sys
from types import SimpleNamespace

import numpy as np
import pytest

from perfbench import check, loadgen, run, spec, traffic
from perfbench.ref import bits as rbits
from perfbench.ref import hll as rhll
from perfbench.system import state_keys

SEED = 2**31 + 77
WINDOW = (0.0, 10.0)
WRITES = 40
STEP = 0.03  # a write sent every 30 ms, acknowledged 10 ms later

HLL = {"space": {"pages": 2, "closed_days": 7, "users": 6,
                 "visitors": 1 << 20},
       "state": [{"type": "hll", "key": "d{day}:{page}",
                  "for": {"day": [0, "closed_days"], "page": [0, "pages"]},
                  "fill": {"elements": 16, "of": "visitors"}},
                 {"type": "hll", "key": "today:{page}",
                  "for": {"page": [0, "pages"]},
                  "fill": {"elements": 16, "of": "visitors"}}],
       "writers": {"connections": 1, "pipeline": 1,
                   "command": ["PFADD", "today:{page}",
                               {"elements": 2, "of": "users"}],
                   "vars": {"page": {"uniform": [0, "pages"]}}}}
BITS = {"space": {"users": 512, "closed_days": 8, "hot": 6},
        "state": [{"type": "bits", "key": "d{day}",
                   "for": {"day": [0, "closed_days"]}, "bits": "users",
                   "fill": {"density": 0.25}},
                  {"type": "bits", "key": "today", "bits": "users",
                   "fill": {"set": ["users-1"]}}],
        "writers": {"connections": 1, "pipeline": 1,
                    "command": ["SETBIT", "today", "{uid}", "1"],
                    "vars": {"uid": {"uniform": [0, "hot"]}}}}

_WAU = "wau:{conn}:{j}"
MIXES = {
    "pfcount_one_key": (HLL, [["PFCOUNT", "today:{page}"]],
                        {"page": {"uniform": [0, "pages"]}}),
    "pfcount_seven_keys": (HLL, [["PFCOUNT"] + [f"d{d}:{{page}}"
                                                for d in range(7)]],
                           {"page": {"uniform": [0, "pages"]}}),
    "bitop_or_week": (BITS, [["BITOP", "OR", _WAU]
                             + [f"d{{day+{d}}}" for d in range(7)],
                             ["BITCOUNT", _WAU], ["DEL", _WAU]],
                      {"day": {"uniform": [0, "closed_days-6"]}}),
    "bitop_or_today": (BITS, [["BITOP", "OR", _WAU, "today", "d{day}"],
                              ["BITCOUNT", _WAU], ["DEL", _WAU]],
                       {"day": {"uniform": [0, "closed_days"]}}),
}


def _base(conf):
    """Every key's state after the fill, from the references."""
    space = conf["space"]
    out = {}
    for key, e, i in state_keys(conf):
        f = e["fill"]
        if e["type"] == "hll":
            ids = traffic.fill_elements(SEED, np.asarray([i]),
                                        int(f["elements"]),
                                        traffic.value(f["of"], space))[0]
            out[key] = rhll.hll_registers(ids)
            continue
        nbits = traffic.value(e["bits"], space)
        st = (np.packbits(traffic.fill_cells(SEED, i, nbits, f["density"]))
              if "density" in f else rbits.make(nbits))
        rbits.set_bits(st, [traffic.value(x, space) for x in f.get("set", [])])
        out[key] = st
    return out


def _answer(cmds, states):
    """A read's right replies (a BITOP destination lives for the read)."""
    scratch, out = {}, []
    for args in cmds:
        get = lambda k: scratch.get(k, states.get(k))  # noqa: E731
        if args[0] == "PFCOUNT":
            regs = np.maximum.reduce([get(k) for k in args[1:]])
            out.append(int(round(rhll.estimate(regs))))
        elif args[0] == "BITOP":
            scratch[args[2]] = rbits.bitop(args[1], *map(get, args[3:]))
            out.append(len(scratch[args[2]]))
        elif args[0] == "BITCOUNT":
            out.append(rbits.bitcount(get(args[1])))
        else:  # DEL of the read's own destination
            out.append(sum(scratch.pop(k, None) is not None
                           for k in args[1:]))
    return out


class History:
    """The records of one run with every reply right: write k is sent at
    STEP * k and acknowledged 10 ms later; read n is sent 20 ms after
    write 3n + 1 and answered 5 ms later, so it sees writes 0..3n+1."""

    def __init__(self, mix: str):
        conf, commands, rvars = MIXES[mix]
        self.conf = conf
        self.cell = spec.Cell(
            name="pinned", chips=1,
            config={"space": conf["space"], "state": conf["state"]},
            traffic={"writers": conf["writers"],
                     "readers": {"connections": 1, "rate": 10,
                                 "commands": commands, "vars": rvars},
                     "limits": {"answer_gap": 1e-5}},
            end_to_end=[])
        self.roles = {n: traffic.Role(n, self.cell.traffic[n],
                                      conf["space"], SEED)
                      for n in ("writers", "readers")}
        states = _base(conf)
        after = []
        replies = []
        for (args,) in self.roles["writers"].make(0, np.arange(WRITES)):
            st = states[args[1]]
            if args[0] == "PFADD":
                b, r = rhll.bucket_rank(np.asarray(args[2], np.uint64))
                replies.append(int(np.any(r > st[b])))
                np.maximum.at(st, b, r)
            else:
                replies.append(int(rbits.get_bits(st, [int(args[2])])[0]))
                rbits.set_bits(st, [int(args[2])])
            after.append({k: v.copy() for k, v in states.items()})
        self.after = after
        self.final = states
        t = STEP * np.arange(WRITES) + 0.001
        self.wrec = {"conn": np.zeros(WRITES, np.int64),
                     "j": np.arange(WRITES, dtype=np.int64),
                     "t_send": t, "t_reply": t + 0.01,
                     "value": np.asarray(replies, np.int64),
                     "err": np.zeros(WRITES, bool)}
        seen = [3 * n + 1 for n in range(WRITES // 3)]
        reads = self.roles["readers"].make(0, np.arange(len(seen)))
        due = STEP * np.asarray(seen) + 0.02
        self.rrec = {"i": np.arange(len(seen), dtype=np.int64),
                     "conn": np.zeros(len(seen), np.int64),
                     "j": np.arange(len(seen), dtype=np.int64),
                     "due": due, "t_send": due, "t_reply": due + 0.005,
                     "err": np.zeros(len(seen), bool),
                     "values": np.asarray([_answer(c, after[k])
                                           for c, k in zip(reads, seen)],
                                          np.int64)}

    def check(self):
        return check.Check(self.cell, SEED, self.roles["writers"],
                           self.roles["readers"], self.wrec, self.rrec,
                           WINDOW)

    def readback(self, ck):
        return [_answer([args], self.final)[0]
                for args in ck.readback_commands()]

    def compare(self, ck, got, control=None):
        info = {"unanswered": 0}
        return run.compare(self.cell, ck, got, info, info,
                           control)["answer_gap"]["value"]


#: (mix, shift of every answer the program gives, read gap, read-back gap)
READS = [
    ("pfcount_one_key", 0, 0.0, 0.0),
    ("pfcount_one_key", 3, 0.1556925443436189, 0.11287960940188481),
    ("pfcount_seven_keys", 0, 0.0, 0.0),
    ("pfcount_seven_keys", -3, 0.026002877177586654,
     0.11423874215510087),
    ("bitop_or_week", 0, 0.0, 0.0),
    ("bitop_or_week", 5, 5.0, 0.7142857142857143),  # DEL: 6 for 1
    ("bitop_or_today", 0, 0.0, 0.0),
    ("bitop_or_today", -2, 2.0, 0.2857142857142857),
]


@pytest.mark.parametrize("mix,shift,read,read_back", READS,
                         ids=[f"{m}-{s:+d}" for m, s, _, _ in READS])
def test_read_and_read_back_gaps(mix, shift, read, read_back):
    h = History(mix)
    h.rrec["values"] += shift
    ck = h.check()
    got = [g + shift for g in h.readback(ck)]
    assert ck.read_gap() == pytest.approx(read, rel=1e-12, abs=0)
    assert ck.readback_gap(got) == pytest.approx(read_back, rel=1e-12,
                                                 abs=0)
    assert h.compare(ck, got) == pytest.approx(max(read, read_back),
                                               rel=1e-12, abs=0)


def _share(log: str, command: str) -> float:
    """The command's write-reply share from compare's `# answer gaps`
    line."""
    return float(re.search(rf"'{command.lower()}_wrong_share': ([^,}}]+)",
                           log).group(1))


def _first(values, want: int) -> int:
    return int(np.nonzero(values == want)[0][0])


#: (mix, fault, control, wrong share, answer_gap). "flip" turns the first
#: PFADD reply of 0 to 1 (no register could change), or the first SETBIT
#: reply of 1 to 0 (the bit was set before).
REPLIES = [
    ("pfcount_one_key", None, False, 0.0, 0.0),
    ("pfcount_one_key", "flip", False, 1 / 40, 1.0),
    ("pfcount_one_key", None, True, 2 / 40, 1.0),
    ("bitop_or_today", None, False, 0.0, 0.0),
    ("bitop_or_today", "flip", False, 1 / 6, 1.0),
    ("bitop_or_today", None, True, 1 / 6, 1.0),
]


@pytest.mark.parametrize(
    "mix,fault,control,share,gap", REPLIES,
    ids=[f"{MIXES[m][0]['writers']['command'][0].lower()}-"
         f"{'control' if c else f or 'right'}" for m, f, c, _, _ in REPLIES])
def test_write_reply_checks(capsys, mix, fault, control, share, gap):
    h = History(mix)
    command = h.conf["writers"]["command"][0]
    if fault:
        v = h.wrec["value"]
        at = _first(v, 0 if command == "PFADD" else 1)
        v[at] = 1 - v[at]
    ck = h.check()
    got = h.readback(ck)
    capsys.readouterr()
    value = h.compare(ck, got, run.CONTROL if control else None)
    assert _share(capsys.readouterr().err, command) == pytest.approx(
        share, rel=1e-12, abs=0)
    assert value == pytest.approx(gap, rel=1e-12, abs=0)


def test_an_unknown_state_type_names_the_file_it_looked_for():
    with pytest.raises(ValueError, match=r"perfbench/state/nosuch\.py"):
        spec.state_type("nosuch")


@pytest.mark.parametrize("tables", [
    ({"WRITES": {"SETBIT": 1}, "READS": {}},
     {"WRITES": {}, "READS": {"setbit": 2}}),
    ({"WRITES": {}, "READS": {"DEL": 1}},),
], ids=["two_types", "a_command_of_the_read"])
def test_a_command_modelled_twice_is_an_error(tables):
    types = {f"t{n}": SimpleNamespace(**t) for n, t in enumerate(tables)}
    with pytest.raises(ValueError, match="modelled by both"):
        spec.command_models(types)


def test_each_command_has_one_model_in_the_harness():
    writes, reads = spec.command_models(spec.state_types())
    assert {"PFADD", "SETBIT"} <= set(writes)
    assert {"PFCOUNT", "BITOP", "BITCOUNT"} <= set(reads)


class MGetBit:
    """A read of the test's own that answers an array: the bits at its
    offsets."""

    rounds = False

    def keys_read(self, args):
        return [args[1]]

    def answer(self, args, states):
        return rbits.get_bits(states[0], [int(a) for a in args[2:]]) \
            .astype(np.float64)


@pytest.mark.parametrize("flip,want", [(False, 0.0), (True, 1.0)],
                         ids=["right", "one_element_wrong"])
def test_an_array_reply_is_checked_element_by_element(monkeypatch, flip,
                                                      want):
    """Reads of BITCOUNT and a command that answers an array, recorded as
    the load generator packs them, held element by element to the SETBIT
    history's bracket."""
    types = dict(spec.state_types(),
                 probe=SimpleNamespace(WRITES={},
                                       READS={"MGETBIT": MGetBit()}))
    monkeypatch.setattr(spec, "state_types", lambda root=spec.ROOT: types)
    h = History("bitop_or_today")
    readers = {"connections": 1, "rate": 10,
               "commands": [["BITCOUNT", "today"],
                            ["MGETBIT", "today", "0", "1", "2", "511"]]}
    h.cell.traffic["readers"] = readers
    h.roles["readers"] = traffic.Role("readers", readers, h.conf["space"],
                                      SEED)
    seen = [3 * n + 1 for n in range(WRITES // 3)]
    rec = []
    for n, k in enumerate(seen):
        st = h.after[k]["today"]
        bits = tuple(int(b) for b in rbits.get_bits(st, [0, 1, 2, 511]))
        t = STEP * k + 0.02
        rec.append((n, 0, n, t, t, t + 0.005, False,
                    [rbits.bitcount(st), bits]))
    if flip:
        rec[4][7][1] = (1 - rec[4][7][1][0],) + rec[4][7][1][1:]
    z = np.load(io.BytesIO(loadgen._pack("readers", rec, {})))
    h.rrec = {k: z[k] for k in z.files if k != "info"}
    assert list(h.rrec["widths"]) == [1, 4]
    assert h.check().read_gap() == want


def test_an_answer_of_another_length_is_wrong():
    assert check.gap([1, 0], [1, 0, 0], [1, 0, 0], False) == 1.0
    assert check.gap([1], 1.0, 1.0, False) == 0.0


def test_state_modules_import_neither_jax_nor_the_program():
    code = ("import sys; sys.path.insert(0, %r); from perfbench import spec; "
            "spec.state_types(); bad = [m for m in sys.modules if "
            "m.split('.')[0] in ('jax', 'redisson_tpu')]; "
            "assert not bad, bad" % spec.ROOT)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True)
    assert out.returncode == 0, out.stderr.decode()[-2000:]
