"""Wire front-end tests: RESP framing, conformance vs the facade,
pipeline reply ordering, shed paths and connection-drop chaos.

The wire server speaks the same RESP bytes as real Redis, so the
bundled interop client (and redis-py, when importable) should observe
results identical to calling the facade directly.
"""

import socket
import time

import numpy as np
import pytest

from redisson_tpu.client import RedissonTPU
from redisson_tpu.config import Config
from redisson_tpu.fault import inject
from redisson_tpu.fault.inject import FaultInjector, FaultPlan, FaultRule
from redisson_tpu.interop.resp_client import SyncRespClient
from redisson_tpu.wire import proto


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def _wire_client(**wire_kw):
    cfg = Config()
    cfg.use_serve()
    tr = cfg.use_trace()
    tr.sample_every = 1
    tr.slowlog_threshold_ms = 0.0
    w = cfg.use_wire()
    for k, v in wire_kw.items():
        setattr(w, k, v)
    return RedissonTPU(cfg)


def _connect(c, **kw):
    cli = SyncRespClient("127.0.0.1", c.wire.port, retry_attempts=1, **kw)
    cli.connect()
    return cli


def _raw_connect(c):
    s = socket.create_connection(("127.0.0.1", c.wire.port), timeout=5.0)
    s.settimeout(5.0)
    return s


def _raw_read_frames(sock, parser, n, deadline_s=5.0):
    """Read exactly n frames from a raw socket, or fewer on EOF."""
    frames = []
    end = time.monotonic() + deadline_s
    while len(frames) < n and time.monotonic() < end:
        try:
            data = sock.recv(65536)
        except socket.timeout:
            break
        if not data:
            break
        frames.extend(parser.feed(data))
    return frames


# ---------------------------------------------------------------------------
# proto: frame rendering + single shared codec
# ---------------------------------------------------------------------------


class TestProto:
    def test_simple_frames(self):
        assert proto.ok() == b"+OK\r\n"
        assert proto.simple("PONG") == b"+PONG\r\n"
        assert proto.integer(42) == b":42\r\n"
        assert proto.bulk(b"ab\r\nc") == b"$5\r\nab\r\nc\r\n"
        assert proto.bulk(None) == b"$-1\r\n"
        assert proto.array([proto.integer(1), proto.bulk(b"x")]) == (
            b"*2\r\n:1\r\n$1\r\nx\r\n"
        )

    def test_err_flattens_newlines(self):
        frame = proto.err("bad\r\nthing", code="ERR")
        assert frame.startswith(b"-ERR ")
        assert frame.endswith(b"\r\n")
        assert frame.count(b"\r\n") == 1

    def test_null_per_protocol(self):
        assert proto.null(proto.RESP2) == b"$-1\r\n"
        assert proto.null(proto.RESP3) == b"_\r\n"

    def test_map_reply_resp2_vs_resp3(self):
        pairs = [(b"a", proto.integer(1))]
        assert proto.map_reply(pairs, proto.RESP2).startswith(b"*2\r\n")
        assert proto.map_reply(pairs, proto.RESP3).startswith(b"%1\r\n")

    def test_redirect_and_busy_frames(self):
        assert proto.moved(100, "1.2.3.4:7000") == b"-MOVED 100 1.2.3.4:7000\r\n"
        assert proto.ask(100, "1.2.3.4:7000") == b"-ASK 100 1.2.3.4:7000\r\n"
        busy = proto.busy("shed", 0.05)
        assert busy.startswith(b"-BUSY retry_after=0.050s")

    def test_roundtrip_through_parser(self):
        p = proto.RespParser()
        frames = p.feed(proto.array([proto.integer(7), proto.bulk(b"hi")]))
        assert frames == [[7, b"hi"]]
        p.close()

    def test_fake_server_uses_shared_codec(self):
        # Satellite 1: one RESP implementation per direction.  The fake
        # interop server's render helpers must BE the proto functions.
        from redisson_tpu.interop import fake_server

        assert fake_server._ok is proto.ok
        assert fake_server._err is proto.err
        assert fake_server._int is proto.integer
        assert fake_server._bulk is proto.bulk
        assert fake_server._array is proto.array

    def test_resp_client_uses_shared_codec(self):
        import redisson_tpu.interop.resp_client as rc

        assert rc.proto is proto
        assert rc.RespError is proto.RespError


# ---------------------------------------------------------------------------
# conformance: wire vs facade on golden vectors
# ---------------------------------------------------------------------------


GOLDEN_HLL = [b"alpha", b"beta", b"gamma", b"\x00\xffbin", b"alpha"]
GOLDEN_BITS = [0, 1, 7, 63, 300]


class TestConformance:
    def test_command_table_matches_facade(self):
        c = _wire_client()
        try:
            cli = _connect(c)
            try:
                # HyperLogLog family over the wire...
                assert cli.execute("PFADD", "w:hll", *GOLDEN_HLL) == 1
                assert cli.execute("PFADD", "w:hll2", b"delta", b"beta") == 1
                wire_count = cli.execute("PFCOUNT", "w:hll")
                wire_union = cli.execute("PFCOUNT", "w:hll", "w:hll2")
                assert cli.execute("PFMERGE", "w:dest", "w:hll", "w:hll2") == b"OK"
                wire_merged = cli.execute("PFCOUNT", "w:dest")

                # ...must equal the same vectors pushed through the facade.
                f = c.get_hyper_log_log("f:hll")
                f.add_all([v for v in GOLDEN_HLL])
                f2 = c.get_hyper_log_log("f:hll2")
                f2.add_all([b"delta", b"beta"])
                assert wire_count == f.count()
                assert wire_union == f.count_with("f:hll2")
                dest = c.get_hyper_log_log("f:dest")
                dest.merge_with("f:hll", "f:hll2")
                assert wire_merged == dest.count()

                # Bitset family.
                for i in GOLDEN_BITS:
                    assert cli.execute("SETBIT", "w:bits", str(i), "1") == 0
                assert cli.execute("SETBIT", "w:bits", "1", "0") == 1
                assert cli.execute("GETBIT", "w:bits", "7") == 1
                assert cli.execute("GETBIT", "w:bits", "1") == 0
                fb = c.get_bit_set("f:bits")
                for i in GOLDEN_BITS:
                    fb.set(i)
                fb.clear(1)
                assert cli.execute("BITCOUNT", "w:bits") == fb.cardinality()

                # Keyspace commands agree with the facade's view.
                assert cli.execute("EXISTS", "w:hll", "w:bits", "w:nope") == 2
                assert cli.execute("DBSIZE") == len(c.keys())
                assert cli.execute("DEL", "w:hll2") == 1
                assert cli.execute("EXISTS", "w:hll2") == 0
            finally:
                cli.close()
        finally:
            c.shutdown()

    def test_bitop_over_wire(self):
        c = _wire_client()
        try:
            cli = _connect(c)
            try:
                cli.execute("SETBIT", "a", "0", "1")
                cli.execute("SETBIT", "a", "3", "1")
                cli.execute("SETBIT", "b", "3", "1")
                cli.execute("SETBIT", "b", "9", "1")
                nbytes = cli.execute("BITOP", "AND", "a", "a", "b")
                assert isinstance(nbytes, int) and nbytes >= 1
                assert cli.execute("BITCOUNT", "a") == 1
                assert cli.execute("GETBIT", "a", "3") == 1
            finally:
                cli.close()
        finally:
            c.shutdown()

    def test_introspection_surface(self):
        c = _wire_client()
        try:
            cli = _connect(c)
            try:
                assert cli.execute("PING") == b"PONG"
                assert cli.execute("ECHO", "hey") == b"hey"
                info = cli.execute("INFO")
                assert b"# wire" in info and b"redis_version" in info
                cli.execute("PFADD", "m:k", "x")
                usage = cli.execute("MEMORY", "USAGE", "m:k")
                assert isinstance(usage, int) and usage > 0
                assert cli.execute("MEMORY", "USAGE", "m:missing") is None
                stats = cli.execute("MEMORY", "STATS")
                assert isinstance(stats, list) and stats
                assert isinstance(cli.execute("MEMORY", "DOCTOR"), bytes)
                assert isinstance(cli.execute("SLOWLOG", "LEN"), int)
                assert isinstance(cli.execute("SLOWLOG", "GET"), list)
                assert cli.execute("SLOWLOG", "RESET") == b"OK"
                assert cli.execute("CLUSTER", "KEYSLOT", "m:k") == (
                    __import__(
                        "redisson_tpu.ops.crc16", fromlist=["key_slot"]
                    ).key_slot(b"m:k")
                )
                assert cli.execute("SELECT", "0") == b"OK"
                assert isinstance(cli.execute("COMMAND", "COUNT"), int)
                assert isinstance(cli.execute("CLIENT", "ID"), int)
                assert cli.execute("CLIENT", "SETNAME", "t1") == b"OK"
                assert cli.execute("CLIENT", "GETNAME") == b"t1"
            finally:
                cli.close()
        finally:
            c.shutdown()

    def test_hello_negotiates_resp3(self):
        c = _wire_client()
        try:
            cli = _connect(c)
            try:
                h2 = cli.execute("HELLO", "2")
                assert isinstance(h2, list)  # RESP2 renders map as flat array
                assert b"proto" in h2
            finally:
                cli.close()
            # The bundled parser is RESP2-only, so check the RESP3 map
            # upgrade at the byte level on a raw socket.
            sock = _raw_connect(c)
            try:
                sock.sendall(proto.resp_encode(b"HELLO", b"3"))
                data = b""
                while b"\r\nmodules\r\n" not in data and b"modules" not in data:
                    chunk = sock.recv(65536)
                    if not chunk:
                        break
                    data += chunk
                assert data.startswith(b"%")  # RESP3 map header
                assert b"proto\r\n:3\r\n" in data
            finally:
                sock.close()
        finally:
            c.shutdown()

    def test_slowlog_entries_carry_admitted_stage(self):
        # admitted_at is stamped at socket read; with slowlog threshold 0
        # every wire op lands in the slowlog with an "admitted" event.
        c = _wire_client()
        try:
            cli = _connect(c)
            try:
                cli.execute("PFADD", "sl:k", "v1", "v2")
                cli.execute("PFCOUNT", "sl:k")
            finally:
                cli.close()
            entries = c.trace.slowlog.get(None)
            assert entries
            names = {ev[0] for e in entries for ev in e.events}
            assert "admitted" in names
        finally:
            c.shutdown()

    def test_redis_py_roundtrip(self):
        redis = pytest.importorskip("redis")
        c = _wire_client()
        try:
            r = redis.Redis(host="127.0.0.1", port=c.wire.port)
            assert r.ping()
            assert r.pfadd("rp:hll", "a", "b", "c") == 1
            assert r.pfcount("rp:hll") == c.get_hyper_log_log("rp:hll").count()
            assert r.setbit("rp:bits", 5, 1) == 0
            assert r.getbit("rp:bits", 5) == 1
            assert r.bitcount("rp:bits") == 1
            r.close()
        finally:
            c.shutdown()


# ---------------------------------------------------------------------------
# reply ordering: the CommandsQueue dual
# ---------------------------------------------------------------------------


class TestReplyOrder:
    def test_pipeline_replies_in_submission_order(self):
        c = _wire_client()
        try:
            cli = _connect(c)
            try:
                cmds, expect = [], []
                for i in range(16):
                    cmds.append(("SETBIT", "ord:bits", str(i), "1"))
                    expect.append(0)
                    cmds.append(("ECHO", "m%d" % i))
                    expect.append(b"m%d" % i)
                out = cli.pipeline(cmds)
                assert out == expect
            finally:
                cli.close()
        finally:
            c.shutdown()

    def test_inline_replies_ordered_behind_engine_commands(self):
        # PING after a PFADD in the same pipeline must not jump the queue
        # even though it needs no engine round-trip.
        c = _wire_client()
        try:
            cli = _connect(c)
            try:
                out = cli.pipeline(
                    [
                        ("PFADD", "q:k", "a"),
                        ("PING",),
                        ("PFCOUNT", "q:k"),
                        ("PING",),
                    ]
                )
                assert out == [1, b"PONG", 1, b"PONG"]
            finally:
                cli.close()
        finally:
            c.shutdown()

    def test_two_connections_do_not_cross_replies(self):
        c = _wire_client()
        try:
            a, b = _connect(c), _connect(c)
            try:
                oa = a.pipeline([("ECHO", "from-a%d" % i) for i in range(8)])
                ob = b.pipeline([("ECHO", "from-b%d" % i) for i in range(8)])
                assert oa == [b"from-a%d" % i for i in range(8)]
                assert ob == [b"from-b%d" % i for i in range(8)]
            finally:
                a.close()
                b.close()
        finally:
            c.shutdown()


# ---------------------------------------------------------------------------
# shedding: inflight cap, connection limit, RejectedError rendering
# ---------------------------------------------------------------------------


class TestShedding:
    def test_inflight_cap_sheds_busy_in_position(self):
        cap = 4
        c = _wire_client(max_inflight_per_conn=cap)
        try:
            # One write carrying 12 frames: the read loop reserves slots
            # for all frames before any completion can drain the window,
            # so frames cap+1.. deterministically shed.
            sock = _raw_connect(c)
            parser = proto.RespParser()
            try:
                total = 12
                payload = b"".join(
                    proto.resp_encode(b"SETBIT", b"shed:bits", str(i).encode(), b"1")
                    for i in range(total)
                )
                sock.sendall(payload)
                frames = _raw_read_frames(sock, parser, total)
                assert len(frames) == total
                busy = [f for f in frames if isinstance(f, proto.RespError)]
                okay = [f for f in frames if not isinstance(f, proto.RespError)]
                assert len(okay) == cap and all(f == 0 for f in okay)
                assert len(busy) == total - cap
                assert all(str(e).startswith("BUSY") for e in busy)
                # Position: accepted commands are exactly the first `cap`.
                assert not any(
                    isinstance(f, proto.RespError) for f in frames[:cap]
                )
                # Shed commands never reached the engine.
                bits = c.get_bit_set("shed:bits")
                assert bits.cardinality() == cap
                assert c.wire.snapshot()["sheds_total"] >= total - cap
            finally:
                parser.close()
                sock.close()
        finally:
            c.shutdown()

    def test_connection_limit_shed(self):
        c = _wire_client(max_connections=1)
        try:
            keeper = _connect(c)
            try:
                sock = _raw_connect(c)
                parser = proto.RespParser()
                try:
                    frames = _raw_read_frames(sock, parser, 1)
                    assert frames and isinstance(frames[0], proto.RespError)
                    assert str(frames[0]).startswith("BUSY")
                    # Server closes the shed connection.
                    assert sock.recv(1) == b""
                finally:
                    parser.close()
                    sock.close()
                # Survivor connection still works.
                assert keeper.execute("PING") == b"PONG"
            finally:
                keeper.close()
        finally:
            c.shutdown()

    def test_rejected_error_renders_busy_with_retry_after(self):
        import types

        from redisson_tpu.serve.errors import RejectedError
        from redisson_tpu.wire.server import WireServer

        stub = types.SimpleNamespace(_cluster=None, sheds_total=0,
                                     redirects_rendered=0)
        state = types.SimpleNamespace(
            exc=RejectedError("queue full", retry_after_s=0.25))
        frame = WireServer._render_error(stub, state)
        assert frame.startswith(b"-BUSY retry_after=0.250s")
        assert stub.sheds_total == 1


# ---------------------------------------------------------------------------
# chaos: wire_conn fault seam
# ---------------------------------------------------------------------------


class TestWireChaos:
    def test_dropped_connection_loses_no_acks(self):
        # Rule fires on the 2nd read of the connection: the first pipeline
        # is fully acknowledged, the second write kills the connection
        # before any of its frames are dispatched.  Delivered replies must
        # be an exact, in-order, correctly-valued prefix.
        inj = FaultInjector(
            FaultPlan(rules=[FaultRule(seam="wire_conn", nth=2, times=1)])
        )
        inject.install(inj)
        c = _wire_client()
        try:
            sock = _raw_connect(c)
            parser = proto.RespParser()
            try:
                first = b"".join(
                    proto.resp_encode(b"SETBIT", b"chaos:bits", str(i).encode(), b"1")
                    for i in range(3)
                )
                sock.sendall(first)
                frames = _raw_read_frames(sock, parser, 3)
                assert frames == [0, 0, 0]  # no lost acks, correct values

                # Second write trips the seam: server drops the connection
                # without processing the frame.
                sock.sendall(proto.resp_encode(b"SETBIT", b"chaos:bits", b"9", b"1"))
                tail = _raw_read_frames(sock, parser, 1, deadline_s=3.0)
                assert tail == []  # EOF, no partial/misattributed reply
            finally:
                parser.close()
                sock.close()

            # Engine state reflects exactly the acknowledged prefix.
            bits = c.get_bit_set("chaos:bits")
            assert bits.cardinality() == 3
            assert bits.get(9) is False
            assert c.wire.snapshot()["dropped_conns"] == 1

            # A fresh connection is unaffected (rule consumed its window).
            cli = _connect(c)
            try:
                assert cli.execute("PING") == b"PONG"
                assert cli.execute("GETBIT", "chaos:bits", "2") == 1
            finally:
                cli.close()
        finally:
            inject.uninstall()
            c.shutdown()

    def test_partial_pipeline_never_misattributed(self):
        # Drop mid-stream on the FIRST read of the second connection while
        # an untouched first connection keeps running: replies seen by the
        # survivor must all be its own.
        inj = FaultInjector(
            FaultPlan(rules=[FaultRule(seam="wire_conn", nth=1, times=1)])
        )
        c = _wire_client()
        try:
            survivor = _connect(c)
            try:
                inject.install(inj)
                try:
                    sock = _raw_connect(c)
                    parser = proto.RespParser()
                    try:
                        sock.sendall(proto.resp_encode(b"ECHO", b"victim"))
                        assert _raw_read_frames(sock, parser, 1, 3.0) == []
                    finally:
                        parser.close()
                        sock.close()
                finally:
                    inject.uninstall()
                out = survivor.pipeline(
                    [("ECHO", "sv%d" % i) for i in range(6)]
                )
                assert out == [b"sv%d" % i for i in range(6)]
            finally:
                survivor.close()
        finally:
            inject.uninstall()
            c.shutdown()


# ---------------------------------------------------------------------------
# lifecycle + observability
# ---------------------------------------------------------------------------


class TestLifecycle:
    def test_shutdown_stops_listener(self):
        c = _wire_client()
        port = c.wire.port
        assert port > 0
        c.shutdown()
        with pytest.raises(OSError):
            socket.create_connection(("127.0.0.1", port), timeout=1.0)

    def test_wire_gauges_registered(self):
        c = _wire_client()
        try:
            cli = _connect(c)
            try:
                cli.pipeline([("PFADD", "g:k", "a"), ("PFCOUNT", "g:k")])
            finally:
                cli.close()
            snap = c.metrics.snapshot()["gauges"]
            names = {k for k in snap if k.startswith("wire.")}
            for want in (
                "wire.connections",
                "wire.commands",
                "wire.engine_commands",
                "wire.pipeline_depth",
                "wire.sheds",
                "wire.dropped_conns",
            ):
                assert want in names, want
            assert snap["wire.commands"] >= 2
            assert snap["wire.engine_commands"] >= 2
        finally:
            c.shutdown()

    def test_auth_gate(self):
        c = _wire_client(password="sekret")
        try:
            sock = _raw_connect(c)
            parser = proto.RespParser()
            try:
                sock.sendall(proto.resp_encode(b"PFADD", b"a:k", b"v"))
                frames = _raw_read_frames(sock, parser, 1)
                assert isinstance(frames[0], proto.RespError)
                assert str(frames[0]).startswith("NOAUTH")
                sock.sendall(proto.resp_encode(b"AUTH", b"wrong"))
                frames = _raw_read_frames(sock, parser, 1)
                assert str(frames[0]).startswith("WRONGPASS")
                sock.sendall(proto.resp_encode(b"AUTH", b"sekret"))
                frames = _raw_read_frames(sock, parser, 1)
                assert frames == [b"OK"]
                sock.sendall(proto.resp_encode(b"PFADD", b"a:k", b"v"))
                frames = _raw_read_frames(sock, parser, 1)
                assert frames == [1]
            finally:
                parser.close()
                sock.close()
        finally:
            c.shutdown()


# ---------------------------------------------------------------------------
# PFADD from the indexed frame: the facade's key layout, no per-element object
# ---------------------------------------------------------------------------


def _pfadd_elements(case):
    rng = np.random.default_rng(29)
    if case == "random_0_300":
        sizes = rng.integers(0, 301, 40)
    elif case == "random_0_256":
        sizes = rng.integers(0, 257, 256)
    else:
        sizes = {"empty": [0], "edge_16": [16, 3], "edge_17": [16, 17],
                 "edge_256": [256, 0, 255], "edge_257": [256, 257],
                 "none": []}[case]
    return [bytes(rng.integers(0, 256, int(n), dtype="uint8"))
            for n in sizes]


@pytest.fixture(scope="module")
def facade():
    c = RedissonTPU(Config())
    yield c
    c.shutdown()


@pytest.mark.parametrize("impl", ["native", "python"])
@pytest.mark.parametrize("case", ["empty", "edge_16", "edge_17", "edge_256",
                                  "edge_257", "none", "random_0_256",
                                  "random_0_300"])
def test_wire_pfadd_payload_matches_encode_batch(case, impl, facade,
                                                 monkeypatch):
    from redisson_tpu import native
    from redisson_tpu.wire import commands as wire_commands

    if impl == "python":
        monkeypatch.setattr(native, "_load", lambda: None)
    values = _pfadd_elements(case)
    parser = proto.RespParser()
    (frame,) = parser.feed_requests(
        proto.resp_encode(b"PFADD", b"p:k", *values))
    assert isinstance(frame, proto.RequestFrame)
    obj = facade.get_hyper_log_log("p:k")
    try:
        want = obj._encode_batch(values)
    except ValueError as exc:
        with pytest.raises(ValueError) as got:
            wire_commands.build(facade, frame)
        assert str(got.value) == str(exc)
        assert "exceeds max width bucket" in str(exc)
        return
    call = wire_commands.build(facade, frame)
    ((key, kind, payload, nkeys),) = call.ops
    assert (key, kind, nkeys) == ("p:k", "hll_add", len(values))
    assert payload["data"].dtype == want[0].dtype
    assert payload["data"].shape == want[0].shape
    assert np.array_equal(payload["data"], want[0])
    assert payload["lengths"].dtype == want[1].dtype == np.int32
    assert np.array_equal(payload["lengths"], want[1])
    assert call.indexed == len(values)


def test_wire_pfadd_with_a_non_bulk_element_goes_through_encode_batch(facade):
    from redisson_tpu.wire import commands as wire_commands

    parser = proto.RespParser()
    (frame,) = parser.feed_requests(
        b"*4\r\n$5\r\nPFADD\r\n$3\r\np:k\r\n:42\r\n$1\r\nx\r\n")
    assert frame == [b"PFADD", b"p:k", 42, b"x"]
    call = wire_commands.build(facade, frame)
    payload = call.ops[0][2]
    want = facade.get_hyper_log_log("p:k")._encode_batch([b"42", b"x"])
    assert payload["data"].tolist() == want[0].tolist()
    assert payload["lengths"].tolist() == want[1].tolist()
    assert call.indexed == 0


def test_indexed_args_and_read_busy_counters_over_a_socket():
    c = _wire_client()
    try:
        cli = _connect(c)
        try:
            ids = [str(10_000_000 + i).encode() for i in range(256)]
            s0 = c.wire.snapshot()
            assert cli.execute("PFADD", "ix:k", *ids) == 1
            s1 = c.wire.snapshot()
            assert cli.execute("SETBIT", "ix:b", "7", "1") == 0
            s2 = c.wire.snapshot()
            assert cli.execute("PFCOUNT", "ix:k") > 0
        finally:
            cli.close()
        assert s1["indexed_args"] - s0["indexed_args"] == 256
        assert s2["indexed_args"] - s1["indexed_args"] == 0
        assert s1["commands_total"] - s0["commands_total"] == 1
        assert 0 < s1["read_busy_ns"] - s0["read_busy_ns"] \
            <= s1["loop_busy_ns"] - s0["loop_busy_ns"]
        assert s2["read_busy_ns"] <= s2["loop_busy_ns"]
    finally:
        c.shutdown()
