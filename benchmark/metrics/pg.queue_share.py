"""Share of an op's wall between arriving at the OSD and its PG logic
running: dispatch_queued, pg_queued, xshard_handoff, pg_locked."""
SOURCE = "program_span"
LAYER = "PG / EC backend"
MOVES = "throughput"


def read(ctx):
    from harness import ledger
    return ledger.hop_share(ctx, ledger.QUEUE_HOPS)
