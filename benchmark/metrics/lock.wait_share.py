"""Seconds of lock.wait sections (a contended TimedLock or the shared
Config's lock, waits above 50 us) over thread-busy seconds."""
SOURCE = "program_span"
LAYER = "locks"
MOVES = "throughput"


def read(ctx):
    from harness import spans
    return spans.share(ctx, ("lock.wait",), "seconds")
