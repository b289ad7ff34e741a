"""Union of the device-op intervals over the traced window."""
SOURCE = "device_trace"
LAYER = "device"
MOVES = "throughput"


def read(ctx):
    red = ctx["trace"]
    if red is None:
        return None
    return 100.0 * red["busy_s"] / red["window_s"]
