"""Self seconds of the pg.* and ec.* sections over thread-busy seconds."""
SOURCE = "program_span"
LAYER = "PG / EC backend"
MOVES = "throughput"


def read(ctx):
    from harness import spans
    return spans.share(ctx, ("pg.", "ec."))
