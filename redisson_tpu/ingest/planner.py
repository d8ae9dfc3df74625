"""Adaptive ingest path planner: measured-at-first-use cost table.

The backends used to hard-wire the insert strategy (`hll_impl` config +
`hostfold_policy` heuristics in `backend_tpu.py`, a parallel copy of
the logic in `bench.py`'s `ingest[auto]` report).  The planner replaces
both: the first batch of a given (structure, size class) on a platform
times every candidate path on synthetic data, records ns/key in a
process-wide table, and every later batch in that class takes the
measured winner.  Host-side candidates the planner cannot time itself
(the native hostfold, whose cost depends on the measured link profile)
are injected per call via `extra_costs`.

Size classes follow the engine's batch buckets (powers of two,
2^10..2^21), so one measurement per bucket the jit cache will ever see.
Measurement batches are capped at 2^18 keys: the per-key cost of the
sort-based paths is within noise of the 2^21 figure and first-use
latency stays ~tens of ms on CPU.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from redisson_tpu.ingest import kernels
from redisson_tpu.ops import hll

# Engine batch buckets (engine.MIN_BUCKET/MAX_BUCKET; mirrored here to
# keep the dependency one-way: engine -> ingest).
_MIN_CLASS = 10
_MAX_CLASS = 21
_MEASURE_CAP = 1 << 18
_REPS = 3

#: device-insert paths the planner can time itself, per structure
DEVICE_PATHS = {
    "hll": ("scatter", "sort", "segment"),
    "bits": ("scatter", "segment"),
}


@dataclasses.dataclass(frozen=True)
class IngestPlan:
    """One planning decision: the chosen path + the costs behind it."""

    path: str
    costs: Dict[str, float]  # ns per key, per candidate
    measured: bool  # False when the path was forced by config


@jax.jit
def _hll_scatter(regs, b, r):
    return regs.at[b].max(r)


_hll_sort = jax.jit(hll.insert_sorted)
_hll_segment = jax.jit(kernels.segmented_hll_add)


@jax.jit
def _bits_scatter(cells, i):
    return cells.at[i].set(jnp.ones_like(i, cells.dtype))


_bits_segment = jax.jit(kernels.segmented_bits_set)


def _synthetic_hll(n: int):
    # Deterministic, well-spread bucket/rank streams (Knuth multiplicative
    # hash of the index) — no RNG so repeated measurements agree.
    i = np.arange(n, dtype=np.uint32)
    bucket = jnp.asarray(((i * np.uint32(2654435761)) % hll.M).astype(np.int32))
    rank = jnp.asarray((i % 50 + 1).astype(np.int32))
    return hll.make(), bucket, rank


def _synthetic_bits(n: int):
    i = np.arange(n, dtype=np.uint32)
    cells_n = 1 << 20
    idx = jnp.asarray(((i * np.uint32(2654435761)) % cells_n).astype(np.int32))
    return jnp.zeros((cells_n,), jnp.uint8), idx


def measure_device_paths(structure: str, n: int) -> Dict[str, float]:
    """Time every device path for one synthetic batch; ns/key each."""
    n = max(1, min(n, _MEASURE_CAP))
    if structure == "hll":
        regs, b, r = _synthetic_hll(n)
        cands = {
            "scatter": (_hll_scatter, (regs, b, r)),
            "sort": (_hll_sort, (regs, b, r)),
            "segment": (_hll_segment, (regs, b, r)),
        }
    elif structure == "bits":
        cells, idx = _synthetic_bits(n)
        cands = {
            "scatter": (_bits_scatter, (cells, idx)),
            "segment": (_bits_segment, (cells, idx)),
        }
    else:
        raise ValueError(f"unknown ingest structure {structure!r}")
    costs = {}
    for name, (fn, args) in cands.items():
        jax.block_until_ready(fn(*args))  # compile outside the timed reps
        best = float("inf")
        for _ in range(_REPS):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            best = min(best, time.perf_counter() - t0)
        costs[name] = best * 1e9 / n
    return costs


class IngestPlanner:
    """Per-process path planner with a lazily measured cost table.

    `measure` is a test seam: `(structure, n) -> {path: ns_per_key}`
    replacing the real timing loop.
    """

    def __init__(
        self,
        platform: Optional[str] = None,
        measure: Optional[Callable[[str, int], Dict[str, float]]] = None,
    ):
        self.platform = platform or jax.default_backend()
        self._measure = measure or measure_device_paths
        self._table: Dict[tuple, Dict[str, float]] = {}
        # Advisory seed costs (a previous process's table, a config hint):
        # NEVER a substitute for the first-use measurement. A stale prior
        # once let `sort` survive in the candidate set at 5x the measured
        # scatter cost (a CPU-fallback bench before PR 1) — so plan() always
        # measures the row on first use and measured values override the
        # prior; a prior only fills paths the measurement cannot time.
        self._priors: Dict[tuple, Dict[str, float]] = {}
        self._lock = threading.Lock()

    def set_prior(self, structure: str, nkeys: int,
                  costs: Dict[str, float]) -> None:
        """Seed advisory ns/key costs for one (structure, size class) row.
        Priors never pre-empt measurement — see __init__."""
        key = (structure, self.size_class(nkeys))
        with self._lock:
            self._priors.setdefault(key, {}).update(costs)

    @staticmethod
    def size_class(nkeys: int) -> int:
        """log2 of the engine batch bucket `nkeys` pads into."""
        c = max(1, int(nkeys) - 1).bit_length()
        return min(max(c, _MIN_CLASS), _MAX_CLASS)

    def plan(
        self,
        structure: str,
        nkeys: int,
        forced: str = "auto",
        extra_costs: Optional[Dict[str, float]] = None,
        device_overhead: float = 0.0,
    ) -> IngestPlan:
        """Pick the insert path for one batch.

        `forced != "auto"` short-circuits (the config knob); otherwise
        the (structure, size class) row is measured on first use and
        the cheapest of device paths + `extra_costs` wins.
        `device_overhead` (ns/key) is added to every device path before
        the comparison — the caller's per-key H2D transfer cost, which
        the kernel-only measurement cannot see but a host-side candidate
        in `extra_costs` (hostfold) does not pay. Window-level candidates
        ride the same dict: the backend prices "tape" (the window
        megakernel) as the delta cost minus its OBSERVED per-key launch
        saving, so the tape only enters the table once the chunked path's
        dispatch cost has actually been measured — never on faith.
        """
        if forced != "auto":
            return IngestPlan(path=forced, costs={}, measured=False)
        key = (structure, self.size_class(nkeys))
        with self._lock:
            costs = self._table.get(key)
        if costs is None:
            # First use of this row: measure EVERY device path now, even
            # ones a prior claims to know — measured values override the
            # prior, so a dominated path (the stale `sort` prior) can never
            # outlive its first real timing. Priors only contribute paths
            # the measurement loop cannot time on this platform.
            fresh = self._measure(structure, 1 << key[1])
            with self._lock:
                row = dict(self._priors.get(key, {}))
                row.update(fresh)
                costs = self._table.setdefault(key, row)
        all_costs = {k: v + device_overhead for k, v in costs.items()}
        if extra_costs:
            all_costs.update(extra_costs)
        best = min(all_costs, key=all_costs.get)
        return IngestPlan(path=best, costs=all_costs, measured=True)

    def table(self) -> Dict[str, Dict[str, float]]:
        """Snapshot for bench/debug reporting: {'hll@16': {...}, ...}."""
        with self._lock:
            return {
                f"{s}@{c}": dict(costs)
                for (s, c), costs in sorted(self._table.items())
            }


_DEFAULT: Optional[IngestPlanner] = None
_DEFAULT_LOCK = threading.Lock()


def default_planner() -> IngestPlanner:
    """Process-wide shared planner (backends + bench share the table)."""
    global _DEFAULT
    with _DEFAULT_LOCK:
        if _DEFAULT is None:
            _DEFAULT = IngestPlanner()
        return _DEFAULT
