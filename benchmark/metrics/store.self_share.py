"""Self seconds of the store.* sections over thread-busy seconds."""
SOURCE = "program_span"
LAYER = "store"
MOVES = "throughput"


def read(ctx):
    from harness import spans
    return spans.share(ctx, ("store.",))
