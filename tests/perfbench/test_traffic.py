"""The traffic generator, the RESP codec and the load generator child."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from perfbench import resp, spec, traffic

ROOT = spec.ROOT


def _role(mix="sharded_ingest", name="writers", seed=7, space=None):
    t = json.load(open(os.path.join(ROOT, "perfbench", "traffic",
                                    mix + ".json")))
    space = space or {"pages": 8192, "users": 1 << 27, "closed_days": 7}
    return traffic.Role(name, t[name], space, seed)


@pytest.mark.parametrize("mix", ["sharded_ingest", "sharded_events",
                                 "sharded_logins"])
def test_commands_are_a_pure_function_of_the_seed(mix):
    a = _role(mix, seed=2**31 + 5).make(3, np.arange(40))
    b = _role(mix, seed=2**31 + 5).make(3, np.arange(40))
    c = _role(mix, seed=2**31 + 6).make(3, np.arange(40))
    enc = [resp.encode(x[0]) for x in a]
    assert enc == [resp.encode(x[0]) for x in b]
    assert enc != [resp.encode(x[0]) for x in c]
    # any command can be re-made alone
    assert resp.encode(_role(mix, seed=2**31 + 5).make(3, [17])[0][0]) \
        == enc[17]


def test_sharded_writers_keep_to_their_own_keys():
    role = _role("sharded_ingest")
    for conn in (0, 5, 63):
        keys = role.arg(conn, np.arange(500))
        pages = np.array([int(k.split(":")[1]) for k in keys])
        assert np.all(pages % 64 == conn) and pages.max() < 8192


def test_zipf_is_skewed_like_ycsb():
    z = traffic.Zipf(8192, 0.99)
    draws = z.draw(traffic.stream(1, np.arange(200_000, dtype=np.uint64)), 3)
    share = np.bincount(draws, minlength=8192)
    top = np.sort(share)[::-1] / len(draws)
    assert 0.08 < top[0] < 0.12  # 1 / zeta(8192, 0.99) ~ 0.10
    assert draws.min() >= 0 and draws.max() < 8192


def test_value_arithmetic():
    env = {"conn": np.array([1, 2]), "connections": 4}
    assert traffic.value("closed_days-6", {"closed_days": 30}) == 24
    assert traffic.value("users/connections", {"users": 64}, env) == 16
    assert list(traffic.value("conn+connections*2", {}, env)) == [9, 10]


def test_resp_encodes_binary_ids_and_parses_replies():
    ids = np.array([0x0A0D0A0D, 2**64 - 1], np.uint64)
    frame = resp.encode(["PFADD", "k", ids])
    assert frame.startswith(b"*4\r\n$5\r\nPFADD\r\n$1\r\nk\r\n$8\r\n")
    assert frame[-14:] == b"$8\r\n" + b"\xff" * 8 + b"\r\n"
    p = resp.Parser()
    got = p.feed(b":1\r\n+OK\r\n-ERR x\r\n$3\r\nabc\r\n*2\r\n:1\r")
    assert got[:4] == [1, "OK", "ERR x", b"abc"]
    assert isinstance(got[2], resp.RespError)
    assert p.feed(b"\n:2\r\n") == [[1, 2]]


@pytest.mark.parametrize("reply,value,failed", [
    (7, 7, False),
    ("OK", 0, False),
    ([1, 0, 1], (1, 0, 1), False),
    ([1, None, 1], 0, True),
    ([1, resp.RespError("ERR x")], 0, True),
    ([b"a"], 0, True),
    (None, 0, True),
    (resp.RespError("BUSY try again"), 0, True),
], ids=["integer", "ok", "array_of_integers", "array_with_nil",
        "array_with_error", "array_of_bulk", "nil", "error"])
def test_load_generator_reply_values(reply, value, failed):
    from perfbench import loadgen

    assert loadgen._value(reply) == (value, failed)


def test_array_replies_keep_their_slots():
    """A read whose commands answer an integer and an array: its records
    keep every element, and the check splits them by command again."""
    import io

    from perfbench import check, loadgen

    rec = [(i, 0, i, 0.1 * i, 0.1 * i, 0.1 * i + 0.01, False,
            [5 + i, (1, 0, i % 2)]) for i in range(3)]
    z = np.load(io.BytesIO(loadgen._pack("readers", rec, {})))
    rr = {k: z[k] for k in z.files if k != "info"}
    assert list(rr["widths"]) == [1, 3] and rr["values"].shape == (3, 4)
    got = check.replies(rr, 1)
    assert list(got[0]) == [6] and list(got[1]) == [1, 0, 1]
    # integers alone keep one slot a command and no widths
    z = np.load(io.BytesIO(loadgen._pack("readers", [r[:7] + ([5, 6],)
                                                      for r in rec], {})))
    assert "widths" not in z.files and z["values"].shape == (3, 2)


def test_resp_parses_a_nil_array_as_nil():
    assert resp.Parser().feed(b"*-1\r\n*0\r\n$-1\r\n") == [None, [], None]


def test_load_generator_child_never_imports_jax():
    code = ("import sys; sys.path.insert(0, %r); import perfbench.loadgen; "
            "assert 'jax' not in sys.modules, sorted(sys.modules)" % ROOT)
    subprocess.run([sys.executable, "-c", code], check=True, timeout=60)
