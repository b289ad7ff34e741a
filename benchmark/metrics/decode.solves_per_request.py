"""Recovery-row systems solved per decode lane request of the window:
``ec.solve_rows`` sections (one around each miss of the process's
cache of recovery rows: a k x k GF(2^w) system inverted and expanded to
bits for one (code, have-set, erased-set); a hit opens nothing) over
the decode lane's requests (the lane counters' difference across the
window).  About 1 where every PG's codec solves for itself and nearly
every read brings a new have-set, towards 0 once the rows of a
signature are solved once a process.  The section's name takes the
``ec`` layer's first part because ``harness/spans.py`` reads a closed
list of layers.  A program without the shared cache opens no such
section: nothing to read."""
SOURCE = "program_span"
LAYER = "PG / EC backend"
MOVES = "throughput"


def read(ctx):
    from harness import spans
    from ceph_tpu.ops import engine
    if not hasattr(engine, "RecoveryRowsCache"):
        return None
    _, red = spans.for_ctx(ctx)
    if red is None or red["busy_s"] <= 0:
        return None                     # a trace that holds no section
    reqs = ctx["lanes_window"]["lanes"]["decode"]["reqs"]
    if reqs <= 0:
        return None
    return red["names"].get("ec.solve_rows", {}).get("count", 0) / reqs
