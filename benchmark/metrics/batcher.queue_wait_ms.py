"""Mean wait of a lane request between its submit and the dispatch of
its group: queue_wait_us over reqs, summed over the window's
batcher.dispatch sections (all three lanes)."""
SOURCE = "program_span"
LAYER = "batcher"
MOVES = "throughput"


def read(ctx):
    from harness import spans
    _, red = spans.for_ctx(ctx)
    row = (red or {}).get("names", {}).get("batcher.dispatch")
    if not row or row["sums"].get("reqs", 0) <= 0:
        return None
    return row["sums"].get("queue_wait_us", 0.0) / row["sums"]["reqs"] / 1e3
