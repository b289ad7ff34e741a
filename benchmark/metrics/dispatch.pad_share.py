"""Share of the bytes staged for the device that are padding: over the
window's dispatch.h2d sections, 1 - live_bytes / bytes.  ``bytes`` is
the whole staging slot a fill hands to the transfer (the batch bucket
and the length quantum included), ``live_bytes`` the payload copied
into it.  Every lane and the CRC path count.  A program whose sections
carry no ``live_bytes`` gives nothing to read."""
SOURCE = "program_span"
LAYER = "dispatch"
MOVES = "throughput"


def read(ctx):
    from harness import spans
    _, red = spans.for_ctx(ctx)
    row = (red or {}).get("names", {}).get("dispatch.h2d", {})
    sums = row.get("sums", {})
    if "live_bytes" not in sums or sums.get("bytes", 0) <= 0:
        return None
    return 100.0 * (1.0 - sums["live_bytes"] / sums["bytes"])
