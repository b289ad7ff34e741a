"""The GF programs' share of their roofline.

Work: what the window's encode, decode and delta lane requests need by
their shapes (harness/work.py), from the lane counters' difference
across the window.  Least time: the larger of bytes over the HBM peak
and int8 operations over the int8 peak (peaks.json, by device_kind).
Time: device time of the trace events inside XLA modules that a
kernels/*.json file with "gf_work": true matches.  Lane requests with
no such event is a malformed run, not a zero.
"""
SOURCE = "device_trace"
LAYER = "kernels"
MOVES = "throughput"


def read(ctx):
    from harness import spec, work
    red = ctx["trace"]
    if red is None:
        return None
    total = work.window_work(ctx["lanes_window"]["lanes"], ctx["ops"],
                             ctx["k"], ctx["m"], ctx["stripe_unit"],
                             max(1, ctx["lost"]))
    if total["requests"] <= 0:
        return None
    gf = [f["family"] for f in spec.kernel_families() if f.get("gf_work")]
    seconds = sum(red["family_seconds"].get(f, 0.0) for f in gf)
    if seconds <= 0:
        raise RuntimeError(
            f"{total['requests']} lane requests ran on the device in the "
            f"window and no trace event matches a GF kernel family; "
            f"modules seen: {sorted(red['unmatched_module_seconds'])}")
    least = work.least_seconds(total, spec.peaks(ctx["device"]["kind"]))
    ctx.setdefault("notes", {})["gf_roofline_binds"] = least["binds"]
    return 100.0 * least["seconds"] / seconds
