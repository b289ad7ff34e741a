"""99th percentile of submit -> ack, from the load generator's stamps."""
SOURCE = "host_clock"
LAYER = "client"
MOVES = "throughput"


def read(ctx):
    from harness import ledger
    return ledger.latency_ms(ctx, 99)
