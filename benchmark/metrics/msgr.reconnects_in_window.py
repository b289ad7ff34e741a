"""Connection attempts of Messenger._reconnect inside the window (one
msgr.reconnect section each): redials of a peer that is gone."""
SOURCE = "program_span"
LAYER = "messenger"
MOVES = "throughput"


def read(ctx):
    from harness import spans
    _, red = spans.for_ctx(ctx)
    if red is None or not red["names"]:
        return None
    return red["names"].get("msgr.reconnect", {"count": 0})["count"]
