"""Share of a read's wall in decode_dispatch + decode_complete: the
shard gather, the reconstruction (on the decode lane where a shard is
lost) and the reassembly.  Read cells."""
SOURCE = "program_span"
LAYER = "PG / EC backend"
MOVES = "throughput"


def read(ctx):
    from harness import ledger
    if ctx["snap"]["hops_read"].get("ops", 0) <= 0:
        return None
    return ledger.hop_share(ctx, ledger.DECODE_HOPS)
