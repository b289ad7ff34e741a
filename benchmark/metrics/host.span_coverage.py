"""Share of the first device's idle time in the window during which at
least one section of the program (ceph_tpu/utils/tracer.py) was open
on some thread: how much of the idle time the trace can explain."""
SOURCE = "device_trace"
LAYER = "host"
MOVES = "throughput"


def read(ctx):
    from harness import spans
    plain, red = spans.for_ctx(ctx)
    if red is None or not red["names"]:
        return None
    share = spans.coverage(red, plain["device_ops"])
    return None if share is None else 100.0 * share
