"""Self seconds of the crc.* sections (crc.host, crc.device) over
thread-busy seconds."""
SOURCE = "program_span"
LAYER = "checksums"
MOVES = "throughput"


def read(ctx):
    from harness import spans
    return spans.share(ctx, ("crc.",))
