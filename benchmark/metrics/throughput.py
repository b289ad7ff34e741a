"""Acknowledged user bytes whose ack fell inside the window, over the
window's whole length.  Never a median of chunks."""
SOURCE = "host_clock"


def read(ctx):
    from harness.loadgen import op_ok
    done = sum(r[7] for r in ctx["window"] if op_ok(r, ctx["ops"]))
    return done / ctx["seconds"] / (1 << 20)
