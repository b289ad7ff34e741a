"""CPU time of all the program's sections in the window over the ops
acknowledged in full in it, in ms: what an op costs the interpreter,
queueing left out.  The probed nests' CPU (the ``cpu_ns`` of the top
section of each) is scaled by thread-busy seconds over their wall.
``None`` where no section carries ``cpu_ns``."""
SOURCE = "program_span"
LAYER = "host"
MOVES = "throughput"


def read(ctx):
    from harness import cpu
    return cpu.ms_per_op(ctx)
