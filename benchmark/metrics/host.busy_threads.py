"""Thread-busy seconds (per thread the union of its sections, summed
over threads) over the window's seconds.  About 1 is one saturated
interpreter; above 1 is time blocked in C or on locks inside
sections."""
SOURCE = "program_span"
LAYER = "host"
MOVES = "throughput"


def read(ctx):
    from harness import spans
    _, red = spans.for_ctx(ctx)
    if red is None or not red["names"] or red["window_s"] <= 0:
        return None
    return red["busy_s"] / red["window_s"]
