"""Executables the process has built for GF row sets over the erasure
signatures its decode lanes have dispatched, both since process start
and read once the window has closed: ``JaxBackend.row_programs_built``
(one per kernel family, row-set shape and input shape where the rows
are an operand; one per row set and input shape where they are a
constant of the program) over the distinct (geometry, have-set,
missing-set) triples in ``EncodeBatcher._dec_signatures``.  A pool's
encode matrix is a row set too, and where a read gathers k of k+m
shards its recovery rows have the encode matrix's shape, so the count
above the line holds the populate's programs as well.  Under 1: the
program count does not follow the signatures.  A tree without the
counters gives nothing to read."""
SOURCE = "program_counter"
LAYER = "dispatch"
MOVES = "throughput"


def read(ctx):
    from ceph_tpu.ec.plugins.tpu import shared_backend
    from ceph_tpu.osd.batcher import EncodeBatcher
    built = getattr(shared_backend(), "row_programs_built", None)
    signatures = len(getattr(EncodeBatcher, "_dec_signatures", ()))
    if built is None or signatures <= 0:
        return None
    return built / signatures
