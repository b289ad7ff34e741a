"""Bytes that crossed between host and device in the window (the
bytes of dispatch.h2d and dispatch.d2h, padding included) over the
user bytes acknowledged in it."""
SOURCE = "program_span"
LAYER = "dispatch"
MOVES = "throughput"


def read(ctx):
    from harness import spans
    from harness.loadgen import op_ok
    _, red = spans.for_ctx(ctx)
    if red is None:
        return None
    rows = [red["names"][n] for n in ("dispatch.h2d", "dispatch.d2h")
            if n in red["names"]]
    user = sum(r[7] for r in ctx["window"] if op_ok(r, ctx["ops"]))
    if not rows or user <= 0:
        return None
    return sum(row["sums"].get("bytes", 0) for row in rows) / user
