"""What the window's lane requests need, by their shapes.

The work of a GF(2^8) matrix code does not depend on what implements
it: every output byte is k (or, for a delta, D) multiply-adds in
GF(2^8), and a multiply-add done as the bit-plane matrix product the
MXU kernel uses is 8 x 8 int8 multiply-accumulates = 128 int8
operations.  Bytes are what has to cross HBM once: the input chunks in
and the produced chunks out.  The shapes follow from the configuration
(k, m, stripe unit) and the traffic (bytes per op), and the counts from
the lane counters' difference across the window.
"""
INT8_OPS_PER_GF_MULADD = 128


def lane_request_work(lane: str, k: int, m: int, io_bytes: int,
                      stripe_unit: int, lost: int = 1) -> dict:
    """Bytes in, bytes out and int8 operations of ONE lane request of
    an op of ``io_bytes`` user bytes."""
    if lane == "encode":                 # a full-stripe write: k -> m
        b_in = io_bytes
        b_out = io_bytes * m // k
        terms = k
    elif lane == "decode":               # a read with `lost` data shards gone
        b_in = io_bytes                  # k surviving chunks per stripe
        b_out = io_bytes * lost // k
        terms = k
    elif lane == "delta":                # a sub-stripe overwrite: D -> m
        cols = min(k, max(1, -(-io_bytes // stripe_unit)))
        b_in = io_bytes                  # old XOR new of the dirty columns
        b_out = io_bytes // cols * m
        terms = cols
    else:
        raise ValueError(f"unknown lane {lane!r}")
    return {"bytes_in": b_in, "bytes_out": b_out,
            "int8_ops": b_out * terms * INT8_OPS_PER_GF_MULADD}


def window_work(lanes_diff: dict, ops: list, k: int, m: int,
                stripe_unit: int, lost: int = 1) -> dict:
    """Total bytes and operations of the device-served lane requests
    of a window.  ``ops`` are the traffic file's op classes: a lane's
    requests are taken to have the size of the op class that feeds it
    (write_full -> encode, write -> delta, read -> decode)."""
    feeds = {"encode": "write_full", "delta": "write", "decode": "read"}
    total = {"bytes": 0, "int8_ops": 0, "requests": 0}
    for lane, counts in lanes_diff.items():
        n = counts["reqs"] - counts["twin_reqs"]
        if n <= 0:
            continue
        sizes = [o["io_bytes"] for o in ops if o["op"] == feeds[lane]]
        if not sizes and lane == "encode":
            # a sub-stripe write that takes the read-modify-write path
            # re-encodes the whole stripes it touches
            width = k * stripe_unit
            sizes = [-(-o["io_bytes"] // width) * width
                     for o in ops if o["op"] == "write"]
        io_bytes = max(sizes)
        w = lane_request_work(lane, k, m, io_bytes, stripe_unit, lost)
        total["bytes"] += n * (w["bytes_in"] + w["bytes_out"])
        total["int8_ops"] += n * w["int8_ops"]
        total["requests"] += n
    return total


def least_seconds(work: dict, peaks: dict) -> dict:
    by_bytes = work["bytes"] / peaks["hbm_bytes_per_s"]
    by_ops = work["int8_ops"] / peaks["int8_ops_per_s"]
    return {"seconds": max(by_bytes, by_ops),
            "binds": "hbm_bandwidth" if by_bytes >= by_ops else "int8_ops"}
