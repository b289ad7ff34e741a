"""Share of the window's row-set dispatches whose binding ran for the
first time: over the ``dispatch.call`` sections that carry ``bound``
(every staged and synchronous call of a GF row set: a pool's encode
matrix, an erasure signature's recovery rows), those with
``bound=new``: the lookup just before the call made the binding (the
row set's bits laid out in plane order on the host and put on the
device, about 2 KiB; no compile).  After the warm-up a pool whose
signatures fit the cache of bindings reads 0; where the cache is
smaller than the signatures in use it evicts and binds again in a
steady state, and this is its churn.  A program whose sections carry
no ``bound`` gives nothing to read."""
SOURCE = "program_span"
LAYER = "dispatch"
MOVES = "throughput"


def read(ctx):
    from harness import spans
    plain, _ = spans.for_ctx(ctx)
    if plain is None:
        return None
    counts = {vals[0]: n for vals, n, _, _ in
              spans.breakdown(plain, "dispatch.call", ("bound",))}
    counts.pop(None, None)
    total = sum(counts.values())
    if total <= 0:
        return None
    return 100.0 * counts.get("new", 0) / total
