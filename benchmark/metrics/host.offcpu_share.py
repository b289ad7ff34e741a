"""100 times (1 - the probed nests' CPU seconds over their wall
seconds): the share of the time the program's threads spent inside
sections that they were off the CPU with work in hand, waiting for the
interpreter lock or in a blocking call.  ``None`` where no section
carries ``cpu_ns``."""
SOURCE = "program_span"
LAYER = "host"
MOVES = "throughput"


def read(ctx):
    from harness import cpu
    return cpu.offcpu_share(ctx)
