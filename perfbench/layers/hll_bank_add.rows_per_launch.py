"""Bank rows one launch of the multi-target HLL insert touches: delta
bank_add_rows / delta bank_add_launches of the backend's counters. The
insert gathers, updates and writes back only those rows, so this is the
width of each launch's block before padding. A program without the
counters reads nothing."""


def read(ctx):
    b = ctx["delta"]["backend"]
    return b["bank_add_rows"] / b["bank_add_launches"] if b.get(
        "bank_add_launches") else None
