"""Share of an op's wall from the PG logic running to the reply
leaving: store_apply, peer_ack_wait, commit_sent (for a write: encode,
the k+m sub-write fan-out and every shard's commit).  Write cells."""
SOURCE = "program_span"
LAYER = "PG / EC backend"
MOVES = "throughput"


def read(ctx):
    from harness import ledger
    if ctx["snap"]["hops_write"].get("ops", 0) <= 0:
        return None
    return ledger.hop_share(ctx, ledger.COMMIT_HOPS)
