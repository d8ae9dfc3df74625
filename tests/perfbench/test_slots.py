"""The benchmark's own key slots (perfbench/slots.py) against the Redis
Cluster specification and against the program's routing function."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from perfbench import slots, spec, traffic
from perfbench.system import state_keys
from redisson_tpu.ops.crc16 import key_slot as program_key_slot


def test_crc16_test_vector():
    assert slots.crc16(b"123456789") == 0x31C3


@pytest.mark.parametrize("key,hashed", [
    ("{user1000}.following", "user1000"),
    ("{user1000}.followers", "user1000"),
    ("foo{}{bar}", "foo{}{bar}"),
    ("foo{{bar}}zap", "{bar"),
    ("foo{bar}{zap}", "bar"),
    ("plain", "plain"),
])
def test_hashtag_rules(key, hashed):
    assert slots.key_slot(key) == slots.crc16(hashed.encode()) % 16384


def test_same_hashtag_same_slot():
    assert (slots.key_slot("{user1000}.following")
            == slots.key_slot("{user1000}.followers"))


def _seeded_keys(n: int):
    rng = np.random.default_rng(25)
    alphabet = np.frombuffer(b"abcdefgh:{}0123456789", np.uint8)
    out = []
    for length in rng.integers(1, 24, n):
        out.append(bytes(alphabet[rng.integers(0, len(alphabet), length)]))
    return out


def test_agrees_with_the_program():
    keys = _seeded_keys(10_000)
    for w in spec.benchmark()["configs"]:
        with open(os.path.join(spec.ROOT, w["file"])) as f:
            keys += [k.encode() for k, _, _ in state_keys(json.load(f))]
    keys += [b"wau:3:17", b"d0:{5}", b"today:{12}x"]
    got = [slots.key_slot(k) for k in keys]
    assert got == [program_key_slot(k) for k in keys]
    assert len(set(got)) > 10_000  # the keys spread over the slots


def test_hashtag_in_a_template():
    """`{{page}}` in a key template renders a hashtag around the page."""
    assert traffic.render("d{day}:{{page}}", {}, {"day": 2, "page": 5},
                          1) == ["d2:{5}"]


def test_command_key_and_slot_map():
    assert slots.command_key(["PFADD", "k", "x"]) == "k"
    assert slots.command_key(["BITOP", "OR", "dest", "a", "b"]) == "dest"
    assert slots.command_key(["PING"]) is None
    reply = [[0, 8191, [b"127.0.0.1", 7001, b"shard-0"]],
             [8192, 16383, [b"127.0.0.1", 7002, b"shard-1"]]]
    m = slots.SlotMap(reply)
    assert m.nodes == [("127.0.0.1", 7001), ("127.0.0.1", 7002)]
    for key in (b"a", b"b", b"{user1000}.x", b"today:7"):
        assert m.node_of(key) == (slots.key_slot(key) >= 8192)
    assert m.node_of(None) == 0
    with pytest.raises(ValueError, match="unserved"):
        slots.SlotMap(reply[:1])


def test_load_generator_imports_neither_program_nor_jax():
    code = ("import sys; import perfbench.slots, perfbench.loadgen; "
            "bad = [m for m in sys.modules if m.split('.')[0] in "
            "('jax', 'redisson_tpu')]; assert not bad, bad")
    out = subprocess.run([sys.executable, "-c", code], cwd=spec.ROOT,
                         capture_output=True, timeout=120)
    assert out.returncode == 0, out.stderr.decode()[-2000:]
