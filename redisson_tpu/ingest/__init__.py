"""Streaming ingest subsystem: the host->device insert path, end to end.

Two pieces, composed by the backends (the path each batch takes is
`backend_tpu.ingest_rule`'s):

* `kernels` — the Pallas segmented-scatter insert kernel (sort keys by
  register index, VMEM-tiled segment-max for HLL / segment-or for bit
  cells) plus a pure-XLA fallback with identical semantics.  Gated on
  `use_pallas()` like every other kernel in `ops/pallas_kernels.py`.
* `pipeline` — a double-buffered staging pipeline that overlaps host
  prep + H2D transfer of batch N+1 with device dispatch of batch N
  (the round-5 host budget showed 4.3 ms of transfer serialized behind
  65 us of dispatch per 1M-key batch).
"""

from redisson_tpu.ingest.kernels import (  # noqa: F401
    hll_insert_segmented,
    hll_insert_segmented_lax,
    bits_insert_segmented,
    bits_insert_segmented_lax,
    segmented_hll_add,
    segmented_bits_set,
)
from redisson_tpu.ingest.pipeline import StagingPipeline  # noqa: F401
