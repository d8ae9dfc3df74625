"""ctypes bindings for the C++ native runtime (native/redisson_native.cpp).

The native library covers the reference's two external native components
(SURVEY.md §2: openhft hash intrinsics + the Netty transport codec):

  * ``murmur3_x64_128`` / ``xxhash64`` — batch hashing of variable-length
    byte keys on host, the ingest path that ships only u64 lanes to the TPU;
  * ``keyslot`` — CRC16 % 16384 with {hashtag} extraction
    (cluster/ClusterConnectionManager.java:543-558 semantics);
  * ``resp_encode_pipeline`` / ``RespParser`` — RESP2 wire codec for the
    durability / Redis-interop client;
  * ``hll_fold`` — one-pass hash+fold into 16384 registers (CPU engine).

The library is compiled on first use (g++, ~1 s) and cached next to the
source. Every entry point has a pure-Python fallback so the package works
on hosts without a toolchain; ``AVAILABLE`` reports which path is live.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
import warnings
from typing import List, Optional, Sequence, Tuple, Union

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_SRC_DIR = os.path.join(_REPO, "native")
_SO_PATH = os.path.join(_SRC_DIR, "librtpu.so")

_lib = None
_lib_lock = threading.Lock()
AVAILABLE = False


def _build() -> Optional[str]:
    src = os.path.join(_SRC_DIR, "redisson_native.cpp")
    if not os.path.exists(src):
        return None
    if os.path.exists(_SO_PATH) and os.path.getmtime(_SO_PATH) >= os.path.getmtime(src):
        return _SO_PATH
    try:
        # The Makefile is the single source of compile flags.
        subprocess.run(
            ["make", "-C", _SRC_DIR, "librtpu.so"],
            check=True, capture_output=True, timeout=120,
        )
        return _SO_PATH if os.path.exists(_SO_PATH) else None
    except (OSError, subprocess.SubprocessError) as exc:
        err = getattr(exc, "stderr", b"") or b""
        warnings.warn(
            f"native build failed, using the pure-Python fallback: {exc!r} "
            f"{err[-2000:].decode(errors='replace')}", RuntimeWarning)
        return None


def _load():
    global _lib, AVAILABLE
    if _lib is not None:
        return _lib
    with _lib_lock:
        if _lib is not None:
            return _lib
        path = _build()
        if path is None:
            return None
        try:
            lib = ctypes.CDLL(path)
        except OSError:
            return None
        u8p = ctypes.POINTER(ctypes.c_uint8)
        i64p = ctypes.POINTER(ctypes.c_int64)
        u64p = ctypes.POINTER(ctypes.c_uint64)
        i32p = ctypes.POINTER(ctypes.c_int32)
        lib.rtpu_murmur3_x64_128_batch.argtypes = [
            u8p, i64p, ctypes.c_int64, ctypes.c_uint64, u64p, u64p]
        lib.rtpu_xxhash64_batch.argtypes = [
            u8p, i64p, ctypes.c_int64, ctypes.c_uint64, u64p]
        lib.rtpu_crc16.argtypes = [u8p, ctypes.c_int64]
        lib.rtpu_crc16.restype = ctypes.c_uint16
        lib.rtpu_keyslot_batch.argtypes = [u8p, i64p, ctypes.c_int64, i32p]
        lib.rtpu_resp_encode_pipeline.argtypes = [
            u8p, i64p, i32p, ctypes.c_int64, i64p]
        lib.rtpu_resp_encode_pipeline.restype = ctypes.c_void_p
        lib.rtpu_free.argtypes = [ctypes.c_void_p]
        lib.rtpu_resp_parser_new.restype = ctypes.c_void_p
        lib.rtpu_resp_parser_free.argtypes = [ctypes.c_void_p]
        lib.rtpu_resp_parser_feed.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64]
        lib.rtpu_resp_parser_feed.restype = ctypes.c_int64
        lib.rtpu_resp_parser_pending.argtypes = [ctypes.c_void_p]
        lib.rtpu_resp_parser_pending.restype = ctypes.c_int64
        lib.rtpu_resp_parser_take.argtypes = [ctypes.c_void_p, u8p, ctypes.c_int64]
        lib.rtpu_resp_parser_take.restype = ctypes.c_int64
        # raw addresses: the wire loop calls this once a socket read
        lib.rtpu_resp_parser_take_requests.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p,
            ctypes.c_void_p, ctypes.c_int64]
        lib.rtpu_resp_parser_take_requests.restype = ctypes.c_int64
        lib.rtpu_hll_fold_batch.argtypes = [
            u8p, i64p, ctypes.c_int64, ctypes.c_uint64, u8p]
        lib.rtpu_hll_fold_u64.argtypes = [
            u64p, ctypes.c_int64, ctypes.c_uint64, u8p, ctypes.c_int32]
        lib.rtpu_hll_fold_rows.argtypes = [
            u8p, ctypes.c_int64, i32p, ctypes.c_int64, ctypes.c_uint64, u8p]
        lib.rtpu_hll_fold_u64_rows.argtypes = [
            u64p, i32p, ctypes.c_int64, ctypes.c_uint64, u8p,
            ctypes.c_int64]
        lib.rtpu_bloom_fold_u64.argtypes = [
            u64p, ctypes.c_int64, ctypes.c_uint64, ctypes.c_int32,
            ctypes.c_uint64, u8p, u8p, ctypes.c_int32]
        lib.rtpu_bloom_contains_u64.argtypes = [
            u64p, ctypes.c_int64, ctypes.c_uint64, ctypes.c_int32,
            ctypes.c_uint64, u8p, u8p, ctypes.c_int32]
        lib.rtpu_bloom_fold_rows.argtypes = [
            u8p, ctypes.c_int64, i32p, ctypes.c_int64, ctypes.c_uint64,
            ctypes.c_int32, ctypes.c_uint64, u8p, u8p]
        lib.rtpu_bloom_contains_rows.argtypes = [
            u8p, ctypes.c_int64, i32p, ctypes.c_int64, ctypes.c_uint64,
            ctypes.c_int32, ctypes.c_uint64, u8p, u8p]
        lib.rtpu_popcount.argtypes = [u8p, ctypes.c_int64]
        lib.rtpu_popcount.restype = ctypes.c_uint64
        lib.rtpu_version.restype = ctypes.c_char_p
        _lib = lib
        AVAILABLE = True
    return _lib


def _concat(keys: Sequence[bytes]) -> Tuple[np.ndarray, np.ndarray]:
    """Concatenate byte keys into (data u8[], offsets i64[n+1])."""
    offsets = np.zeros(len(keys) + 1, np.int64)
    if keys:
        np.cumsum(np.fromiter((len(k) for k in keys), np.int64, len(keys)),
                  out=offsets[1:])
    data = np.frombuffer(b"".join(keys), np.uint8) if keys else np.zeros(0, np.uint8)
    return np.ascontiguousarray(data), offsets


def _u8p(a: np.ndarray):
    if a.size == 0:
        # NULL is fine: every native loop guards on n/len first.
        return ctypes.cast(0, ctypes.POINTER(ctypes.c_uint8))
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))


def murmur3_x64_128(keys: Sequence[bytes], seed: int = 0) -> Tuple[np.ndarray, np.ndarray]:
    """Batch MurmurHash3 x64 128 -> (h1, h2) uint64 arrays."""
    lib = _load()
    if lib is None:
        from redisson_tpu.native._pyfallback import murmur3_x64_128 as g
        pairs = [g(k, seed) for k in keys]
        return (np.array([p[0] for p in pairs], np.uint64),
                np.array([p[1] for p in pairs], np.uint64))
    data, offsets = _concat(keys)
    n = len(keys)
    h1 = np.empty(n, np.uint64)
    h2 = np.empty(n, np.uint64)
    lib.rtpu_murmur3_x64_128_batch(
        _u8p(data), offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        n, ctypes.c_uint64(seed),
        h1.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        h2.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)))
    return h1, h2


def xxhash64(keys: Sequence[bytes], seed: int = 0) -> np.ndarray:
    lib = _load()
    if lib is None:
        from redisson_tpu.native._pyfallback import xxhash64 as g
        return np.array([g(k, seed) for k in keys], np.uint64)
    data, offsets = _concat(keys)
    out = np.empty(len(keys), np.uint64)
    lib.rtpu_xxhash64_batch(
        _u8p(data), offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        len(keys), ctypes.c_uint64(seed),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)))
    return out


def crc16(data: bytes) -> int:
    lib = _load()
    if lib is None:
        from redisson_tpu.ops import crc16 as _pycrc
        return _pycrc.crc16(data)
    buf = np.frombuffer(data, np.uint8)
    return int(lib.rtpu_crc16(_u8p(np.ascontiguousarray(buf)), len(data)))


def keyslot(key: Union[str, bytes]) -> int:
    """CRC16({hashtag-or-key}) % 16384 — Redis cluster slot."""
    if isinstance(key, str):
        key = key.encode()
    lib = _load()
    if lib is None:
        from redisson_tpu.ops import crc16 as _pycrc
        return _pycrc.key_slot(key)
    data, offsets = _concat([key])
    out = np.empty(1, np.int32)
    lib.rtpu_keyslot_batch(
        _u8p(data), offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        1, out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
    return int(out[0])


def keyslot_batch(keys: Sequence[bytes]) -> np.ndarray:
    lib = _load()
    if lib is None:
        from redisson_tpu.ops import crc16 as _pycrc
        return np.array([_pycrc.key_slot(k) for k in keys], np.int32)
    data, offsets = _concat(keys)
    out = np.empty(len(keys), np.int32)
    lib.rtpu_keyslot_batch(
        _u8p(data), offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        len(keys), out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
    return out


# ---------------------------------------------------------------------------
# RESP2 codec
# ---------------------------------------------------------------------------

def _as_arg(a) -> bytes:
    if isinstance(a, bytes):
        return a
    if isinstance(a, str):
        return a.encode()
    if isinstance(a, (int, float)):
        return repr(a).encode() if isinstance(a, float) else str(a).encode()
    return bytes(a)


def resp_encode(*args) -> bytes:
    """Encode one command (RESP array of bulk strings)."""
    return resp_encode_pipeline([args])


def resp_encode_pipeline(commands: Sequence[Sequence]) -> bytes:
    """Encode many commands into one wire buffer (pipeline)."""
    flat: List[bytes] = []
    counts = np.empty(len(commands), np.int32)
    for i, cmd in enumerate(commands):
        enc = [_as_arg(a) for a in cmd]
        counts[i] = len(enc)
        flat.extend(enc)
    lib = _load()
    if lib is None:
        out = bytearray()
        k = 0
        for i in range(len(commands)):
            out += b"*%d\r\n" % counts[i]
            for _ in range(counts[i]):
                a = flat[k]; k += 1
                out += b"$%d\r\n" % len(a) + a + b"\r\n"
        return bytes(out)
    data, offsets = _concat(flat)
    out_len = ctypes.c_int64()
    ptr = lib.rtpu_resp_encode_pipeline(
        _u8p(data), offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        counts.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        len(commands), ctypes.byref(out_len))
    try:
        return ctypes.string_at(ptr, out_len.value)
    finally:
        lib.rtpu_free(ptr)


class RespError(Exception):
    """A Redis `-ERR ...` reply."""


class RespParser:
    """Incremental RESP2 parser. feed(data) -> list of completed replies.

    Replies decode as: bytes (bulk/simple strings), int, None (null bulk /
    null array), list (arrays, recursively), RespError instances for error
    replies (returned, not raised — the client decides).
    """

    def __init__(self):
        lib = _load()
        self._lib = lib
        self._h = lib.rtpu_resp_parser_new() if lib is not None else None
        self._pybuf = bytearray()  # fallback path buffer
        self._pypos = 0  # parse cursor into _pybuf (avoids O(N^2) re-slicing)
        self._poisoned = False  # fallback protocol-violation latch

    def close(self):
        if self._h is not None:
            self._lib.rtpu_resp_parser_free(self._h)
            self._h = None

    def __del__(self):  # pragma: no cover
        try:
            self.close()
        except Exception:
            pass

    def feed(self, data: bytes) -> List:
        if self._lib is None:
            return self._feed_py(data)
        n = self._feed_native(data)
        if n == 0:
            return []
        pend = self._lib.rtpu_resp_parser_pending(self._h)
        out = np.empty(pend, np.uint8)
        got = self._lib.rtpu_resp_parser_take(self._h, _u8p(out), pend)
        assert got == pend
        return self._unflatten(out.tobytes(), n)

    def feed_requests(self, data: bytes) -> List:
        """feed() for the request direction: a frame that is an array of
        bulk (or simple) strings comes out as a :class:`RequestFrame`, every
        other frame as feed() decodes it."""
        if self._lib is None:
            return [_request_frame(item) for item in self._feed_py(data)]
        n = self._feed_native(data)
        if n == 0:
            return []
        pend = self._lib.rtpu_resp_parser_pending(self._h)
        bodies = np.empty(pend, np.uint8)
        # every argument's header alone takes 9 bytes of the stream
        cap = pend // 9
        index = np.empty(3 * n + 2 * cap, np.int64)
        at = index.ctypes.data
        nargs = self._lib.rtpu_resp_parser_take_requests(
            self._h, bodies.ctypes.data, pend, at, 3 * n,
            at + 8 * 3 * n, at + 8 * (3 * n + cap), cap)
        assert nargs >= 0
        offs = index[3 * n:3 * n + cap]
        lens = index[3 * n + cap:]
        body = bodies.tobytes()
        out = []
        for count, a, b in index[:3 * n].reshape(n, 3).tolist():
            if count >= 0:
                out.append(RequestFrame(body, offs, lens, a, count))
            else:
                out.append(self._unflatten(body[a:b], 1)[0])
        return out

    def _feed_native(self, data: bytes) -> int:
        if self._h is None:
            raise ValueError("RespParser is closed")
        if not isinstance(data, bytes):
            data = bytes(data)
        return self._lib.rtpu_resp_parser_feed(self._h, data, len(data))

    @staticmethod
    def _unflatten(stream: bytes, count: int) -> List:
        pos = 0

        def one():
            nonlocal pos
            t = stream[pos:pos + 1]
            payload = int.from_bytes(stream[pos + 1:pos + 9], "little", signed=True)
            pos += 9
            if t == b":":
                return payload
            if t in (b"+", b"$", b"-"):
                if t == b"$" and payload < 0:
                    return None
                body = stream[pos:pos + payload]
                pos += payload
                if t == b"-":
                    return RespError(body.decode("utf-8", "replace"))
                return body
            if t == b"*":
                if payload < 0:
                    return None
                return [one() for _ in range(payload)]
            raise ValueError(f"bad flat type {t!r}")

        return [one() for _ in range(count)]

    # Pure-python incremental parser (fallback).
    def _feed_py(self, data: bytes) -> List:
        if self._poisoned:
            return []
        self._pybuf += data
        out = []
        try:
            while True:
                item, consumed = self._parse_py(self._pybuf, self._pypos)
                if consumed == 0:
                    break
                out.append(item)
                self._pypos += consumed
        except ValueError:
            # Framing lost: surface one in-band error (matching the native
            # parser's poisoning) and drop the rest of the stream.
            self._poisoned = True
            self._pybuf = bytearray()
            self._pypos = 0
            out.append(RespError("ERR protocol violation (bad header or nesting)"))
            return out
        if self._pypos > (1 << 16) and self._pypos * 2 > len(self._pybuf):
            del self._pybuf[:self._pypos]
            self._pypos = 0
        return out

    _MAX_DEPTH = 64  # mirror the native parser's nesting cap

    def _parse_py(self, b: bytes, pos: int, depth: int = 0):
        if pos >= len(b):
            return None, 0
        eol = b.find(b"\r\n", pos + 1)
        if eol < 0:
            return None, 0
        t = bytes(b[pos:pos + 1])
        line = bytes(b[pos + 1:eol])
        after = eol + 2
        if t == b"+":
            return line, after - pos
        if t == b"-":
            return RespError(line.decode("utf-8", "replace")), after - pos
        if t == b":":
            return int(line), after - pos
        if t == b"$":
            n = int(line)
            if n < 0:
                return None, after - pos
            if len(b) < after + n + 2:
                return None, 0
            return bytes(b[after:after + n]), after - pos + n + 2
        if t == b"*":
            if depth >= self._MAX_DEPTH:
                raise ValueError("RESP nesting too deep")
            n = int(line)
            if n < 0:
                return None, after - pos
            items = []
            cur = after
            for _ in range(n):
                item, consumed = self._parse_py(b, cur, depth + 1)
                if consumed == 0:
                    return None, 0
                items.append(item)
                cur += consumed
            return items, cur - pos
        raise ValueError(f"bad RESP header {t!r}")


class RequestFrame:
    """One command frame, an array of bulk strings, held as an index: the
    argument bodies lie back to back in `data` (shared by the frames of one
    socket read) at `offs[first + i]`, `lens[first + i]` bytes long. An
    argument becomes a `bytes` object only when it is asked for; `run`
    hands a command a run of arguments as arrays, with no object per
    argument."""

    __slots__ = ("data", "offs", "lens", "first", "n")

    def __init__(self, data: bytes, offs: np.ndarray, lens: np.ndarray,
                 first: int, n: int):
        self.data = data
        self.offs = offs
        self.lens = lens
        self.first = first
        self.n = n

    def __len__(self) -> int:
        return self.n

    def __getitem__(self, i):
        if isinstance(i, slice):
            return [self[j] for j in range(self.n)[i]]
        j = self.first + range(self.n)[i]  # IndexError past either end
        o = int(self.offs[j])
        return self.data[o:o + int(self.lens[j])]

    def __iter__(self):
        return iter(self[:])

    def run(self, start: int) -> Tuple[np.ndarray, np.ndarray]:
        """(bodies, lengths) of arguments start..n-1: their bytes back to
        back as a uint8 view of `data`, and an int64 view of their
        lengths."""
        a, b = self.first + start, self.first + self.n
        lens = self.lens[a:b]
        if a >= b:
            return np.zeros(0, np.uint8), lens
        o = int(self.offs[a])
        end = int(self.offs[b - 1] + self.lens[b - 1])
        return np.frombuffer(self.data, np.uint8, end - o, o), lens


def _request_frame(item):
    """A decoded frame as feed_requests() gives it: a list of strings
    becomes a RequestFrame, anything else stays as it is."""
    if not isinstance(item, list) or \
            not all(isinstance(a, bytes) for a in item):
        return item
    lens = np.fromiter(map(len, item), np.int64, len(item))
    offs = np.zeros(len(item), np.int64)
    np.cumsum(lens[:-1], out=offs[1:])
    return RequestFrame(b"".join(item), offs, lens, 0, len(item))


def hll_fold(keys: Sequence[bytes], regs: np.ndarray, seed: int = 0) -> np.ndarray:
    """Hash keys and fold max-ranks into a 16384-register uint8 array
    in-place (native) — the CPU twin of the device insert kernel."""
    assert regs.dtype == np.uint8 and regs.shape == (16384,)
    lib = _load()
    if lib is None:
        from redisson_tpu.native._pyfallback import murmur3_x64_128 as g
        for k in keys:
            h1, _ = g(k, seed)
            bucket = h1 & 16383
            rest = h1 >> 14
            rank = 1
            while rank <= 50 and not (rest & 1):
                rest >>= 1
                rank += 1
            if rank > regs[bucket]:
                regs[bucket] = rank
        return regs
    data, offsets = _concat(keys)
    lib.rtpu_hll_fold_batch(
        _u8p(data), offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        len(keys), ctypes.c_uint64(seed), _u8p(regs))
    return regs


def hll_fold_u64(
    keys: np.ndarray, regs: np.ndarray, seed: int = 0, nthreads: int = 0
) -> np.ndarray:
    """Fold u64 keys (hashed as 8-byte LE murmur3_x64_128) into a
    16384-register uint8 array in-place — the transfer-adaptive ingest
    path's host half (ship the 16 KB sketch, not 8 B/key; the merge runs
    on device). Accepts uint64 [n] or the pack_u64 uint32 [n, 2] layout
    (same memory). Releases the GIL for the native call, so the fold
    overlaps the submitting thread. nthreads=0 -> os.cpu_count()."""
    assert regs.dtype == np.uint8 and regs.shape == (16384,)
    if keys.dtype == np.uint64:
        keys = np.ascontiguousarray(keys)
    elif keys.dtype == np.uint32 and keys.ndim == 2 and keys.shape[1] == 2:
        keys = np.ascontiguousarray(keys).view(np.uint64).reshape(-1)
    else:
        # Anything else (e.g. default int64) would truncate through a u32
        # cast and pair adjacent values into garbage keys — a silently
        # skewed estimate. Refuse.
        raise TypeError(
            f"hll_fold_u64 wants uint64 [n] or packed uint32 [n, 2] keys, "
            f"got {keys.dtype} {keys.shape}"
        )
    if nthreads <= 0:
        nthreads = os.cpu_count() or 1
    lib = _load()
    if lib is None:
        from redisson_tpu.native._pyfallback import murmur3_x64_128 as g
        for k in keys.tolist():
            h1, _ = g(int(k).to_bytes(8, "little"), seed)
            bucket = h1 & 16383
            rest = (h1 >> 14) | (1 << 50)
            rank = 1
            while not (rest & 1):
                rest >>= 1
                rank += 1
            if rank > regs[bucket]:
                regs[bucket] = rank
        return regs
    lib.rtpu_hll_fold_u64(
        keys.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        keys.shape[0], ctypes.c_uint64(seed), _u8p(regs),
        ctypes.c_int32(nthreads))
    return regs


def hll_fold_u64_rows(keys: np.ndarray, rows: np.ndarray,
                      bank: np.ndarray, seed: int = 0) -> np.ndarray:
    """Fold u64 keys into per-row sketches of a host bank mirror
    ([nrows, 16384] uint8, in place) — the host half of the sharded-bank
    streaming ingest (ship the folded bank periodically, not 8 B/key).
    Requires the native library (callers gate on available())."""
    assert bank.dtype == np.uint8 and bank.ndim == 2 and bank.shape[1] == 16384
    # in-place raw-pointer writes: a strided view would be corrupted at
    # wrong offsets (and a copy would lose the caller's updates) — refuse
    assert bank.flags.c_contiguous, "bank mirror must be C-contiguous"
    keys = _norm_u64_keys(keys, "hll_fold_u64_rows")
    rows = np.ascontiguousarray(rows, np.int32)
    assert rows.shape[0] == keys.shape[0]
    lib = _load()
    if lib is None:
        raise RuntimeError("native library unavailable")
    lib.rtpu_hll_fold_u64_rows(
        keys.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        rows.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        keys.shape[0], ctypes.c_uint64(seed), _u8p(bank), bank.shape[0])
    return bank


def hll_fold_rows(
    data: np.ndarray, lengths: np.ndarray, regs: np.ndarray, seed: int = 0
) -> Optional[np.ndarray]:
    """Fold padded byte-key rows ([n, w] uint8 + [n] int32 lengths) into a
    16384-register uint8 array in-place. Returns None when the native
    library is unavailable (callers fall back to the device path; unlike
    hll_fold_u64 there is no python fallback worth running per-key here)."""
    assert regs.dtype == np.uint8 and regs.shape == (16384,)
    lib = _load()
    if lib is None:
        return None
    data = np.ascontiguousarray(data, np.uint8)
    lengths = np.ascontiguousarray(lengths, np.int32)
    lib.rtpu_hll_fold_rows(
        _u8p(data), data.shape[1],
        lengths.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        data.shape[0], ctypes.c_uint64(seed), _u8p(regs))
    return regs


def _norm_u64_keys(keys: np.ndarray, who: str) -> np.ndarray:
    """uint64 [n] or pack_u64 uint32 [n, 2] -> contiguous uint64 [n]."""
    if keys.dtype == np.uint64:
        return np.ascontiguousarray(keys)
    if keys.dtype == np.uint32 and keys.ndim == 2 and keys.shape[1] == 2:
        return np.ascontiguousarray(keys).view(np.uint64).reshape(-1)
    raise TypeError(
        f"{who} wants uint64 [n] or packed uint32 [n, 2] keys, "
        f"got {keys.dtype} {keys.shape}"
    )


def bloom_fold_u64(keys: np.ndarray, bits: np.ndarray, k: int, m: int,
                   seed: int = 0, want_newly: bool = True,
                   nthreads: int = 0) -> Optional[np.ndarray]:
    """Fold u64 keys into a packed bloom bitmap in-place (numpy packbits
    big-endian layout; index walk identical to ops/bloom.py indexes()).
    Returns the per-key newly-set mask (uint8 [n]) when want_newly, else
    None. The transfer-adaptive bloom ingest's host half: ship/OR the
    bitmap once instead of 8 B/key + per-key bools over a slow link.
    Requires the native library (callers gate on available())."""
    assert bits.dtype == np.uint8 and bits.shape == ((m + 7) // 8,)
    keys = _norm_u64_keys(keys, "bloom_fold_u64")
    lib = _load()
    if lib is None:
        raise RuntimeError("native library unavailable")
    if nthreads <= 0:
        nthreads = os.cpu_count() or 1
    newly = np.empty(keys.shape[0], np.uint8) if want_newly else None
    lib.rtpu_bloom_fold_u64(
        keys.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        keys.shape[0], ctypes.c_uint64(seed), ctypes.c_int32(k),
        ctypes.c_uint64(m), _u8p(bits),
        _u8p(newly) if newly is not None else None,
        ctypes.c_int32(nthreads))
    return newly


def bloom_contains_u64(keys: np.ndarray, bits: np.ndarray, k: int, m: int,
                       seed: int = 0, nthreads: int = 0) -> np.ndarray:
    """Membership probe of u64 keys against a packed bitmap -> uint8 [n]."""
    assert bits.dtype == np.uint8 and bits.shape == ((m + 7) // 8,)
    keys = _norm_u64_keys(keys, "bloom_contains_u64")
    lib = _load()
    if lib is None:
        raise RuntimeError("native library unavailable")
    if nthreads <= 0:
        nthreads = os.cpu_count() or 1
    out = np.empty(keys.shape[0], np.uint8)
    lib.rtpu_bloom_contains_u64(
        keys.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        keys.shape[0], ctypes.c_uint64(seed), ctypes.c_int32(k),
        ctypes.c_uint64(m), _u8p(bits), _u8p(out), ctypes.c_int32(nthreads))
    return out


def bloom_fold_rows(data: np.ndarray, lengths: np.ndarray, bits: np.ndarray,
                    k: int, m: int, seed: int = 0,
                    want_newly: bool = True) -> Optional[np.ndarray]:
    """Byte-key ([n, w] + lengths) bloom fold into a packed bitmap."""
    assert bits.dtype == np.uint8 and bits.shape == ((m + 7) // 8,)
    lib = _load()
    if lib is None:
        raise RuntimeError("native library unavailable")
    data = np.ascontiguousarray(data, np.uint8)
    lengths = np.ascontiguousarray(lengths, np.int32)
    newly = np.empty(data.shape[0], np.uint8) if want_newly else None
    lib.rtpu_bloom_fold_rows(
        _u8p(data), data.shape[1],
        lengths.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        data.shape[0], ctypes.c_uint64(seed), ctypes.c_int32(k),
        ctypes.c_uint64(m), _u8p(bits),
        _u8p(newly) if newly is not None else None)
    return newly


def bloom_contains_rows(data: np.ndarray, lengths: np.ndarray,
                        bits: np.ndarray, k: int, m: int,
                        seed: int = 0) -> np.ndarray:
    """Byte-key membership probe against a packed bitmap -> uint8 [n]."""
    assert bits.dtype == np.uint8 and bits.shape == ((m + 7) // 8,)
    lib = _load()
    if lib is None:
        raise RuntimeError("native library unavailable")
    data = np.ascontiguousarray(data, np.uint8)
    lengths = np.ascontiguousarray(lengths, np.int32)
    out = np.empty(data.shape[0], np.uint8)
    lib.rtpu_bloom_contains_rows(
        _u8p(data), data.shape[1],
        lengths.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        data.shape[0], ctypes.c_uint64(seed), ctypes.c_int32(k),
        ctypes.c_uint64(m), _u8p(bits), _u8p(out))
    return out


def popcount(bits: np.ndarray) -> int:
    """Population count of a packed uint8 buffer (host BITCOUNT)."""
    lib = _load()
    bits = np.ascontiguousarray(bits, np.uint8)
    if lib is None:
        return int(np.unpackbits(bits).sum())
    return int(lib.rtpu_popcount(_u8p(bits), bits.shape[0]))


def version() -> str:
    lib = _load()
    if lib is None:
        return "python-fallback"
    return lib.rtpu_version().decode()


def available() -> bool:
    _load()
    return AVAILABLE
