"""Share of a read's wall in read_queued + shard_read.  Read cells."""
SOURCE = "program_span"
LAYER = "PG / EC backend"
MOVES = "throughput"


def read(ctx):
    from harness import ledger
    if ctx["snap"]["hops_read"].get("ops", 0) <= 0:
        return None
    return ledger.hop_share(ctx, ledger.SHARD_READ_HOPS)
