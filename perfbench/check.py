"""The comparison that decides `correct`: what the timed path answered,
against a plain numpy model of the same commands (perfbench/ref).

The model holds every key of the configuration as its fill made it, and
replays the writers' commands, re-made from the seed, in the order their
replies came. Under concurrent writes a read may see any write that was
sent before its reply and must see every write acknowledged before it was
sent, so a read's answer is held to the bracket between those two states
(every read modelled is monotone in the writes modelled). After the
window, a sample of the keys written is read back over the wire and held
to the model's final state. The write replies of those keys are held to
what some serial order can give. What each command does, and which write
replies no serial order gives, is its model's, found by name in the
state types' modules (perfbench/state/<type>.py; spec.py lists what such
a module holds). run.compare folds these into the one number compared,
`answer_gap`: the widest relative distance of a read or read-back answer
outside its bracket (element by element for an array reply), and 1 where
a write reply no serial order gives, or a command never answered. run.py
adds a second number, `failed_replies`, held to 0: an error or nil reply
in the window is a wrong answer, whatever the program meant by it (a
shed, an OOM).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from perfbench import spec, traffic

READ_SAMPLE = 256
READBACK_SAMPLE = 32
READBACK_HOT = 8


class Model:
    """Key states of one run: the fill, then the writes."""

    def __init__(self, config: Dict[str, Any], seed: int):
        from perfbench.system import state_keys

        self.space = config["space"]
        self.seed = seed
        self.keys = {k: (e, i) for k, e, i in state_keys(config)}
        self.types = {t: spec.state_type(t)
                      for t in {e["type"] for e in config["state"]}}
        self._base: Dict[str, np.ndarray] = {}

    def base(self, key: str) -> np.ndarray:
        """The key's state after the fill (a copy-safe cached array)."""
        if key not in self._base:
            e, i = self.keys[key]
            self._base[key] = self.type_of(key).base(e, i, self.seed,
                                                     self.space)
        return self._base[key]

    def type_of(self, key: str):
        """The module of the key's state type."""
        return self.types[self.keys[key][0]["type"]]


class Writes:
    """The writers' records with each write's key and payload re-made."""

    def __init__(self, role: traffic.Role, rec: Dict[str, np.ndarray],
                 models: Dict[str, Any]):
        if len(role.commands) != 1:
            raise ValueError("a write is one command")
        self.role = role
        self.rec = rec
        self.cmd = role.commands[0][0].upper()
        if self.cmd not in models:
            raise ValueError(f"the model cannot replay {self.cmd}")
        self.model = models[self.cmd]
        n = len(rec["j"])
        self.key = np.empty(n, object)
        for conn in np.unique(rec["conn"]):
            sel = np.nonzero(rec["conn"] == conn)[0]
            self.key[sel] = role.arg(int(conn), rec["j"][sel])
        self.ok = ~rec["err"]

    def payload(self, idx: np.ndarray) -> List[Any]:
        """What writes `idx` add, as their command's model says."""
        out = [None] * len(idx)
        conns = self.rec["conn"][idx]
        for conn in np.unique(conns):
            at = np.nonzero(conns == conn)[0]
            cmds = self.role.make(int(conn), self.rec["j"][idx[at]])
            for k, c in zip(at, cmds):
                out[k] = self.model.payload(c[0])
        return out


class KeyHistory:
    """One key's states at chosen instants under the writes to it."""

    def __init__(self, model: Model, writes: Writes, key: str):
        self.model = model
        self.key = key
        self.apply = writes.model.apply
        idx = np.nonzero(writes.key == key)[0]
        self.idx = idx
        self.t_send = writes.rec["t_send"][idx]
        self.t_reply = writes.rec["t_reply"][idx]
        self.ok = writes.ok[idx]
        self.pay = writes.payload(idx) if len(idx) else []

    def base(self) -> np.ndarray:
        return self.model.base(self.key)

    def states_at(self, times: np.ndarray, upper: bool,
                  drop: Optional[np.ndarray] = None) -> List[np.ndarray]:
        """The state at each instant: with the writes acknowledged before
        it (upper=False), or with every write sent before it (upper=True).
        `drop` masks writes the state loses (the control)."""
        base = self.base()
        if upper:
            t, use = self.t_send, np.ones(len(self.idx), bool)
        else:
            t, use = self.t_reply, self.ok.copy()
        if drop is not None:
            use &= ~drop
        order = np.argsort(t, kind="stable")
        order = order[use[order]]
        st = base.copy()
        out = [None] * len(times)
        w = 0
        for q in np.argsort(times, kind="stable"):
            while w < len(order) and t[order[w]] < times[q]:
                self.apply(st, self.pay[order[w]])
                w += 1
            out[q] = st.copy() if len(times) > 1 else st
        return out


def _hashable(arg):
    return arg.tobytes() if isinstance(arg, np.ndarray) else arg


def answer(reads: Dict[str, Any], cmds: List[list], state,
           memo=None) -> List[Any]:
    """The model's answer to one read (its commands in order) with key
    states from `state(key)`; a key that a command of the read makes (its
    model's `makes`) lives for the read, and DEL of it answers how many
    it deleted. `memo` caches results by the command, its arguments
    other than keys, and the identity of the states it reads."""
    memo = {} if memo is None else memo
    scratch: Dict[str, np.ndarray] = {}

    def get(k):
        return scratch[k] if k in scratch else state(k)

    def cached(tag, srcs, fn):
        mk = tag + tuple(id(x) for x in srcs)
        if mk not in memo:
            memo[mk] = (fn(), srcs)  # holding srcs keeps their ids unique
        return memo[mk][0]

    out = []
    for args in cmds:
        name = args[0].upper()
        if name == "DEL":
            out.append(float(sum(scratch.pop(k, None) is not None
                                 for k in args[1:])))
            continue
        if name not in reads:
            raise ValueError(f"the model cannot answer {name}")
        m = reads[name]
        keys = m.keys_read(args)
        dest = m.makes(args) if hasattr(m, "makes") else None
        tag = (name,) + tuple(
            _hashable(a) for a in args[1:]
            if not (isinstance(a, str) and (a in keys or a == dest)))
        srcs = [get(k) for k in keys]
        if dest is not None:
            scratch[dest] = cached(("made",) + tag, srcs,
                                   lambda: m.make(args, srcs))
        out.append(cached(tag, srcs, lambda: m.answer(args, srcs)))
    return out


def gap(got, lo, hi, rounds: bool) -> float:
    """Relative distance of `got` outside [lo, hi], the widest element's
    for an array reply; an answer the program rounds to an integer may
    lie half a unit out. An answer with another number of elements than
    the model's is wrong."""
    got, lo, hi = (np.asarray(x, np.float64) for x in (got, lo, hi))
    if got.size != lo.size:
        return 1.0
    got = got.reshape(lo.shape)
    slack = 0.5 if rounds else 0.0
    below = (lo - slack - got) / np.maximum(lo, 1.0)
    above = (got - hi - slack) / np.maximum(hi, 1.0)
    return float(np.max(np.maximum(np.maximum(below, above), 0.0),
                        initial=0.0))


def replies(rec: Dict[str, np.ndarray], n: int) -> List[Any]:
    """Read `n`'s replies, one a command: an integer, or the int64 array
    of an array reply, as the load generator's slots hold them."""
    row = rec["values"][n]
    if "widths" not in rec:
        return list(row)
    return np.split(row, np.cumsum(rec["widths"])[:-1])


class Check:
    """Reads to check, read-back keys, and the comparison."""

    def __init__(self, cell, seed: int, writers: traffic.Role,
                 readers: traffic.Role, wrec, rrec, window: Tuple[float, float]):
        self.cell = cell
        self.seed = seed
        self.model = Model(cell.config, seed)
        write_models, self.reads = spec.command_models(spec.state_types())
        self.writes = Writes(writers, wrec, write_models)
        self.readers = readers
        self.rrec = rrec
        self.window = window
        self._hist: Dict[str, KeyHistory] = {}
        self.worst = None  # (gap, read commands, answer, low, high)
        rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 17])
        t0, t1 = window
        in_win = np.nonzero((rrec["due"] >= t0) & (rrec["due"] < t1)
                            & ~rrec["err"])[0]
        n = min(READ_SAMPLE, len(in_win))
        self.read_idx = np.sort(rng.choice(in_win, n, replace=False))
        self.read_cmds = self._read_cmds(self.read_idx)
        self.readback_keys = self._readback_keys(rng)

    def _read_cmds(self, idx):
        out = [None] * len(idx)
        conns = self.rrec["conn"][idx]
        for conn in np.unique(conns):
            at = np.nonzero(conns == conn)[0]
            for k, c in zip(at, self.readers.make(
                    int(conn), self.rrec["j"][idx[at]])):
                out[k] = c
        return out

    def _readback_keys(self, rng) -> List[str]:
        ok = self.writes.ok
        keys, counts = np.unique(self.writes.key[ok].astype(str),
                                 return_counts=True)
        hot = list(keys[np.argsort(-counts, kind="stable")[:READBACK_HOT]])
        rest = [k for k in keys if k not in set(hot)]
        n = min(READBACK_SAMPLE - len(hot), len(rest))
        return hot + list(rng.choice(rest, n, replace=False)) if n else hot

    def readback_commands(self) -> List[list]:
        return [self.model.type_of(k).readback(k)
                for k in self.readback_keys]

    def history(self, key: str) -> KeyHistory:
        if key not in self._hist:
            self._hist[key] = KeyHistory(self.model, self.writes, key)
        return self._hist[key]

    def _rounds(self, args) -> bool:
        m = self.reads.get(args[0].upper())
        return bool(m is not None and m.rounds)

    # -- the numbers --------------------------------------------------------

    def read_gap(self, lag_s: float = 0.0) -> float:
        """Widest gap of the sampled window reads. `lag_s` > 0 answers them
        from state that much older instead of taking the program's answers
        (the control's stale reads)."""
        rr = self.rrec
        worst = 0.0
        keyed: Dict[str, List[int]] = {}
        for n, cmds in enumerate(self.read_cmds):
            for k in _keys_read(self.reads, cmds):
                keyed.setdefault(k, []).append(n)
        lo_st: Dict[Tuple[str, int], np.ndarray] = {}
        hi_st: Dict[Tuple[str, int], np.ndarray] = {}
        stale: Dict[Tuple[str, int], np.ndarray] = {}
        for k, ns in keyed.items():
            h = self.history(k)
            ns = sorted(set(ns))
            t_send = rr["t_send"][self.read_idx[ns]]
            t_reply = rr["t_reply"][self.read_idx[ns]]
            if not len(h.idx):
                for n in ns:
                    lo_st[k, n] = hi_st[k, n] = stale[k, n] = h.base()
                continue
            for n, s in zip(ns, h.states_at(t_send, upper=False)):
                lo_st[k, n] = s
            for n, s in zip(ns, h.states_at(t_reply, upper=True)):
                hi_st[k, n] = s
            if lag_s:
                for n, s in zip(ns, h.states_at(t_send - lag_s, upper=False)):
                    stale[k, n] = s
        memo: Dict[tuple, Any] = {}
        for n, cmds in enumerate(self.read_cmds):
            lo = answer(self.reads, cmds, lambda k: lo_st[k, n], memo)
            hi = answer(self.reads, cmds, lambda k: hi_st[k, n], memo)
            ans = (answer(self.reads, cmds, lambda k: stale[k, n], memo)
                   if lag_s else replies(rr, self.read_idx[n]))
            for args, a, l, h in zip(cmds, ans, lo, hi):
                g = gap(a, np.minimum(l, h), np.maximum(l, h),
                        self._rounds(args))
                if g > worst or self.worst is None:
                    worst = g
                    self.worst = (g, args[:4],
                                  float(a) if np.ndim(a) == 0 else a, l, h)
        return worst

    def readback_gap(self, got: List[Any], drop_every: int = 0) -> float:
        """Widest gap of the read-back. `drop_every` > 0 answers from a
        state that lost every n-th acknowledged write (the control)."""
        worst = 0.0
        for k, args, g in zip(self.readback_keys, self.readback_commands(),
                              got):
            h = self.history(k)
            inf = np.asarray([np.inf])
            lo = answer(self.reads, [args],
                        lambda _: h.states_at(inf, upper=False)[0])[0]
            hi = answer(self.reads, [args],
                        lambda _: h.states_at(inf, upper=True)[0])[0]
            if drop_every:
                drop = np.zeros(len(h.idx), bool)
                drop[::drop_every] = True
                g = answer(self.reads, [args], lambda _: h.states_at(
                    inf, upper=False, drop=drop)[0])[0]
            if isinstance(g, list) and all(isinstance(x, int) for x in g):
                g = np.asarray(g)
            elif not isinstance(g, (int, float, np.ndarray)):
                return 1.0
            worst = max(worst, gap(g, lo, hi, self._rounds(args)))
        return worst

    def write_wrong_share(self, control: Dict[str, Any]) -> float:
        """Share of the read-back keys' write replies that no serial order
        gives, by the writers' command model (its `wrong`); `control`
        (run.CONTROL) puts the control's replies in the program's place."""
        wrong = checked = 0
        for k in self.readback_keys:
            h = self.history(k)
            w, c = self.writes.model.wrong(h, self.writes.rec["value"][h.idx],
                                           control)
            wrong, checked = wrong + w, checked + c
        return wrong / max(checked, 1)


def _keys_read(reads: Dict[str, Any], cmds: List[list]) -> List[str]:
    """Keys of the configuration that one read's commands read (a key
    that the read makes is the read's own, not a key of the state)."""
    out, made = [], set()
    for args in cmds:
        name = args[0].upper()
        if name == "DEL" and set(args[1:]) <= made:
            continue
        if name not in reads:
            raise ValueError(f"the model cannot answer {name}")
        m = reads[name]
        if hasattr(m, "makes"):
            made.add(m.makes(args))
        out += [k for k in m.keys_read(args) if k not in made]
    return out
