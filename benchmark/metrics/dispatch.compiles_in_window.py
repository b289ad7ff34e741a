"""Programs JAX lowered inside the window (its own monitoring event,
counted whether or not the persistent cache then served them).  The
warm-up load is there to make this 0."""
SOURCE = "program_counter"
LAYER = "dispatch"
MOVES = "throughput"


def read(ctx):
    return ctx["compiles_in_window"]
