"""Self seconds of the batcher.* and dispatch.* sections, the host side
of the device path, over thread-busy seconds."""
SOURCE = "program_span"
LAYER = "batcher"
MOVES = "throughput"


def read(ctx):
    from harness import spans
    return spans.share(ctx, ("batcher.", "dispatch."))
