"""Self seconds of the msgr.* sections over thread-busy seconds."""
SOURCE = "program_span"
LAYER = "messenger"
MOVES = "throughput"


def read(ctx):
    from harness import spans
    return spans.share(ctx, ("msgr.",))
