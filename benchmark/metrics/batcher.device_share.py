"""Lane requests served on the device over all lane requests of the
window (dump_device 'lanes', difference across the window)."""
SOURCE = "program_counter"
LAYER = "batcher"
MOVES = "throughput"


def read(ctx):
    lanes = ctx["lanes_window"]["lanes"].values()
    reqs = sum(v["reqs"] for v in lanes)
    if reqs <= 0:
        return None
    return 100.0 * (reqs - sum(v["twin_reqs"] for v in lanes)) / reqs
