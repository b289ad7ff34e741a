"""Share of the primaries' op seconds spent waiting for and in the
encode (or parity-delta) batch: dump_critical_path stages
batcher_queue + encode.  Write cells."""
SOURCE = "program_span"
LAYER = "PG / EC backend"
MOVES = "throughput"


def read(ctx):
    from harness import ledger
    return ledger.stage_share(ctx, ("batcher_queue", "encode"))
