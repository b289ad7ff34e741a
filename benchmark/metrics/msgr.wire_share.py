"""Share of an op's wall on the wire legs (request: msgr_enqueue,
wire_sent, recv; reply: the client_complete interval)."""
SOURCE = "program_span"
LAYER = "messenger"
MOVES = "throughput"


def read(ctx):
    from harness import ledger
    return ledger.hop_share(ctx, ledger.WIRE_HOPS)
