"""Median submit -> ack, from the load generator's own stamps.  In a
closed loop this is depth over throughput: a per-layer reading, not an
end-to-end metric."""
SOURCE = "host_clock"
LAYER = "client"
MOVES = "throughput"


def read(ctx):
    from harness import ledger
    return ledger.latency_ms(ctx, 50)
