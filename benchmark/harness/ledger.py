"""Shares of an op's wall time from the program's own ledgers.

The hop ledger (utils/hops.py) charges every interval between two
stamps of an op to the hop that ends it, so a window's hop seconds sum
to its op seconds; the client's accumulators see the whole
client -> store -> client path.  A share is hop seconds over op
seconds, both as differences across the window.
"""
WIRE_HOPS = ("msgr_enqueue", "wire_sent", "recv", "client_complete")
QUEUE_HOPS = ("dispatch_queued", "pg_queued", "xshard_handoff", "pg_locked")
SHARD_READ_HOPS = ("read_queued", "shard_read")
DECODE_HOPS = ("decode_dispatch", "decode_complete")
COMMIT_HOPS = ("store_apply", "peer_ack_wait", "commit_sent")


def hop_share(ctx: dict, hops) -> float:
    """Percent of the window's op wall (client view, reads and writes
    together) charged to ``hops``; None where no op completed."""
    wall = part = 0.0
    for view in ("hops_write", "hops_read"):
        d = ctx["snap"][view]
        wall += d.get("op_seconds", 0.0)
        part += sum(d.get("hop_seconds", {}).get(h, 0.0) for h in hops)
    if wall <= 0:
        return None
    return 100.0 * part / wall


def stage_share(ctx: dict, stages) -> float:
    """Percent of the primaries' op seconds (dump_critical_path)
    charged to ``stages``; None where no op retired."""
    crit = ctx["snap"]["critical_path"]
    wall = crit.get("op_seconds_total", 0.0)
    if wall <= 0:
        return None
    return 100.0 * sum(crit["stage_seconds"].get(s, 0.0)
                       for s in stages) / wall


def latency_ms(ctx: dict, q: float) -> float:
    import numpy as np
    lats = [r[1] - r[0] for r in ctx["window"]]
    if not lats:
        return None
    return float(np.percentile(lats, q)) * 1e3
