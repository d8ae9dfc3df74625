"""95th percentile of every write op's enqueue -> dispatch wait in the
window, in ms, unsampled: the deltas of pipeline_stats()'
`write_wait_le_us.<bound>` bucket counts (3 % buckets; the percentile is
the upper bound of the bucket that holds it)."""

import math

PREFIX = "write_wait_le_us."


def read(ctx):
    counts = sorted((int(k[len(PREFIX):]), n)
                    for k, n in ctx["delta"]["executor"].items()
                    if k.startswith(PREFIX) and n > 0)
    total = sum(n for _, n in counts)
    if not total:
        return None
    rank, seen = math.ceil(0.95 * total), 0
    for upper_us, n in counts:
        seen += n
        if seen >= rank:
            return upper_us / 1e3
