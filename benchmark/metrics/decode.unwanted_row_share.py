"""Share of the chunk rows the window's decode dispatches produced
that no rider asked for: over the window's ``batcher.dispatch``
sections of the decode lane, 1 - ``rows_wanted`` / ``rows_out`` (the
keywords carry what the lane adds to its ``dec_rows_wanted`` and
``dec_rows_out`` counters at each dispatch).  The plug-in's decode
entry reconstructs every chunk absent from what a read gathered, so a
read of k of k+m shards gets m rows where it lost one or two.  A
program whose sections carry no ``rows_out`` gives nothing to read."""
SOURCE = "program_span"
LAYER = "batcher"
MOVES = "throughput"


def read(ctx):
    from harness import spans
    _, red = spans.for_ctx(ctx)
    sums = (red or {}).get("names", {}).get("batcher.dispatch", {}) \
        .get("sums", {})
    if sums.get("rows_out", 0) <= 0:
        return None
    return 100.0 * (1.0 - sums.get("rows_wanted", 0) / sums["rows_out"])
