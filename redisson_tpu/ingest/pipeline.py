"""Double-buffered host->device staging pipeline.

For a 1M-key batch the H2D transfer can outweigh host prep and the
device dispatch (a pre-PR-1 host budget found it so; not re-measured on
this round's chip), and it used to serialize ahead of every dispatch.  The
pipeline runs *stage* (prep + `device_put`, the expensive host part)
on a worker thread one batch ahead of *dispatch* (the jitted insert,
cheap to issue, ordered), so batch N+1's transfer overlaps batch N's
device work.  Dispatch stays on the caller's thread because the bank
carry makes it inherently serial.

`trace` collects (event, index, perf_counter) tuples — the overlap
test asserts `("stage_start", N+1)` lands before `("dispatch_end", N)`.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Any, Callable, Iterable, List, Optional, Tuple

from redisson_tpu.fault import inject as fault_inject

TraceEvent = Tuple[str, int, float]

_STOP = object()


class StagingPipeline:
    """Overlap host staging of batch N+1 with device dispatch of batch N.

    `depth` bounds how many staged batches may sit ready ahead of the
    dispatcher (2 = classic double buffering: one in flight on device,
    one staged, one being staged).
    """

    def __init__(self, depth: int = 2, trace: Optional[List[TraceEvent]] = None):
        if depth < 1:
            raise ValueError(f"pipeline depth must be >= 1, got {depth}")
        self.depth = depth
        self.trace = trace

    def _mark(self, event: str, index: int) -> None:
        if self.trace is not None:
            self.trace.append((event, index, time.perf_counter()))

    def run(
        self,
        chunks: Iterable[Any],
        stage: Callable[[Any], Any],
        dispatch: Callable[[int, Any], Any],
    ) -> List[Any]:
        """stage(chunk) on the worker thread; dispatch(i, staged) here.

        Returns dispatch results in order.  A staging exception is
        re-raised on the caller's thread after in-flight dispatches
        drain; a dispatch exception stops the worker promptly.
        """
        q: "queue.Queue" = queue.Queue(maxsize=self.depth)
        stop = threading.Event()
        failure: List[BaseException] = []

        def worker() -> None:
            try:
                for i, chunk in enumerate(chunks):
                    if stop.is_set():
                        return
                    self._mark("stage_start", i)
                    # Fault seam: an injected (or real) H2D failure raises
                    # out of the worker and re-raises on the caller's
                    # thread below — i.e. inside the dispatcher's staging
                    # try, where fault.classify maps it to RetryableFault
                    # (nothing committed yet).
                    fault_inject.fire("stage_h2d")
                    staged = stage(chunk)
                    self._mark("stage_end", i)
                    q.put((i, staged))
            except BaseException as exc:  # noqa: BLE001 - re-raised below
                failure.append(exc)
            finally:
                q.put(_STOP)

        t = threading.Thread(target=worker, name="ingest-stager", daemon=True)
        t.start()
        results: List[Any] = []
        try:
            while True:
                item = q.get()
                if item is _STOP:
                    break
                i, staged = item
                self._mark("dispatch_start", i)
                results.append(dispatch(i, staged))
                self._mark("dispatch_end", i)
        finally:
            stop.set()
            # Keep draining until the worker exits: it may be parked on a
            # full queue (early dispatch failure) with more puts pending.
            while t.is_alive():
                try:
                    q.get_nowait()
                except queue.Empty:
                    t.join(0.01)
            t.join()
        if failure:
            raise failure[0]
        return results
