"""Object base classes (reference: `RedissonObject.java` — name + codec +
executor triple; every object is stateless client-side)."""

from __future__ import annotations

from typing import Iterable, List, Sequence

import numpy as np

from redisson_tpu.codecs import Codec, encode_key


def map_future(f, fn):
    """Chain a decode step onto an executor future (async mirrors return
    decoded values, like the reference's reply convertors)."""
    from concurrent.futures import Future

    out = Future()

    def done(src):
        if src.cancelled():
            out.cancel()
            return
        exc = src.exception()
        if exc is not None:
            out.set_exception(exc)
        else:
            try:
                out.set_result(fn(src.result()))
            except Exception as e:  # decode error
                out.set_exception(e)

    f.add_done_callback(done)
    note = getattr(f, "_note_mapped", None)
    if note is not None:
        # Staged batch future: register the decode wrapper so
        # RBatch.execute() can return decoded values; forward the hook so
        # chained decodes keep pointing at the same batch slot.
        note(out)
        out._note_mapped = note
    return out


def pack_u64(values) -> "np.ndarray":
    """uint64 keys -> their raw little-endian uint32 view [n, 2]
    ([:, 0]=lo, [:, 1]=hi): the zero-copy device-ingest layout shared by
    the HLL and Bloom int fast paths.

    BORROW CONTRACT: when `values` is already uint64-contiguous no copy is
    taken — the caller of the enqueueing API must not mutate the source
    array until the op's future resolves (copy first to reuse the buffer;
    the byte-key APIs always copy)."""
    import numpy as np

    values = np.ascontiguousarray(values, np.uint64)
    return values.view(np.uint32).reshape(-1, 2)


def pack_keys(bodies: np.ndarray, lengths: np.ndarray,
              width_buckets: Sequence[int]) -> tuple:
    """Keys given as their bytes back to back (uint8 `bodies`) and their
    `lengths` -> ([N, W] uint8 zero-padded, [N] int32 lengths).

    W is the smallest width bucket holding the longest key, so repeated
    batches of similar keys reuse one compiled kernel. The facade and the
    wire both lay keys out here, so a value lands in the same registers
    whichever way it came.
    """
    lengths = lengths.astype(np.int32, copy=False)
    n = lengths.shape[0]
    max_len = int(lengths.max()) if n else 1
    w = next((b for b in width_buckets if b >= max_len), None)
    if w is None:
        raise ValueError(
            f"key length {max_len} exceeds max width bucket "
            f"{width_buckets[-1]}"
        )
    data = np.zeros((n, w), np.uint8)
    if n:
        # Vectorized fill: a row-major boolean mask selects exactly the
        # first len(k) cells of each row, in concatenation order — one
        # C-level scatter instead of a per-key python loop (which
        # bounded string-key ingest at ~240K keys/s).
        data[np.arange(w, dtype=np.int32)[None, :] < lengths[:, None]] = bodies
    return data, lengths


class RObject:
    """name + codec + executor; all state lives behind the executor."""

    def __init__(self, name: str, executor, codec: Codec, key_width_buckets: Sequence[int] = (16, 32, 64, 128, 256)):
        self.name = name
        self._executor = executor
        self._codec = codec
        self._width_buckets = tuple(key_width_buckets)

    # -- key encoding -------------------------------------------------------

    def _encode_batch(self, values: Iterable) -> tuple:
        """values -> ([N, W] uint8 zero-padded, [N] int32 lengths), as
        `pack_keys` lays them out."""
        keys: List[bytes] = [encode_key(v, self._codec) for v in values]
        lengths = np.fromiter(map(len, keys), np.int32, len(keys))
        return pack_keys(np.frombuffer(b"".join(keys), np.uint8), lengths,
                         self._width_buckets)

    # -- RObject surface (RObjectAsync mirrored with _async suffix) ---------

    def delete(self) -> bool:
        return self.delete_async().result()

    def delete_async(self):
        return self._executor.execute_async(self.name, "delete", None)

    def is_exists(self) -> bool:
        return self._executor.execute_sync(self.name, "exists", None)

    def get_name(self) -> str:
        """Reference getName() (also available as the `.name` attribute)."""
        return self.name

    def rename(self, new_name: str) -> None:
        """RENAME: move this object's state under a new key; this handle
        follows it (reference rename mutates the object's name too)."""
        self._executor.execute_sync(self.name, "rename", {"newkey": new_name})
        self.name = new_name

    def renamenx(self, new_name: str) -> bool:
        """RENAMENX: rename only when the destination is absent — a single
        atomic op (the check+move runs serialized on the dispatcher, like
        the server-side RENAMENX)."""
        ok = self._executor.execute_sync(
            self.name, "rename", {"newkey": new_name, "nx": True})
        if ok:
            self.name = new_name
        return bool(ok)
