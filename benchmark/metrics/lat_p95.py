"""95th percentile of submit -> ack over ALL ops acked in the window."""
SOURCE = "host_clock"


def read(ctx):
    from harness import ledger
    return ledger.latency_ms(ctx, 95)
