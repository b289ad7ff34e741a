"""Self CPU seconds of the pg.* and ec.* sections (their ``cpu_ns``
less their child sections') over the CPU seconds of all sections,
both within the probed nests.  ``None`` where no section carries
``cpu_ns``."""
SOURCE = "program_span"
LAYER = "PG / EC backend"
MOVES = "throughput"


def read(ctx):
    from harness import cpu
    return cpu.share(ctx, "pg")
