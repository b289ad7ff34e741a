"""Lane requests per batched call, over the three lanes: what the
coalescing window buys."""
SOURCE = "program_counter"
LAYER = "batcher"
MOVES = "throughput"


def read(ctx):
    reqs = sum(v["reqs"] for v in ctx["lanes_window"]["lanes"].values())
    calls = sum(ctx["lanes_window"]["calls"].values())
    if reqs <= 0 or calls <= 0:
        return None
    return reqs / calls
