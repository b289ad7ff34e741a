"""What a run records beside its metrics, for the spread study: how
the acks were spread over the window, how long the cyclic collector
stopped the process, how fast the host was.  Printed in the ``info``
line before the result; the driver reads none of it.
"""
import gc
import statistics
import time


class GcClock:
    """Seconds the cyclic collector ran, by generation (gc.callbacks);
    it stops every thread of the process while it runs."""

    def __init__(self):
        self.pauses = []                # (start, seconds, generation)
        self._t = 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = time.monotonic()
        else:
            self.pauses.append((self._t, time.monotonic() - self._t,
                                info.get("generation", -1)))

    def in_window(self, t0: float, seconds: float) -> dict:
        mine = [p for p in self.pauses if t0 <= p[0] < t0 + seconds]
        return {"collections": len(mine),
                "seconds": sum(p[1] for p in mine),
                "longest_s": max((p[1] for p in mine), default=0.0),
                "gen2": sum(1 for p in mine if p[2] == 2)}


def host_speed_probe() -> float:
    """Seconds a fixed pure-Python loop takes on this host right now:
    the machine shares its cores, and a slow run should be told apart
    from a slow machine."""
    t = time.perf_counter()
    x = 0
    for i in range(2_000_000):
        x += i & 3
    return time.perf_counter() - t


def ack_study(window) -> dict:
    """Per run: completion waves and the largest gap between acks (the
    spread study's columns).  A wave ends where no ack comes for a
    quarter of the median latency."""
    acks = sorted(r[1] for r in window)
    if len(acks) < 2:
        return {"acks": len(acks)}
    lats = sorted(r[1] - r[0] for r in window)
    lat = statistics.median(lats)
    gaps = [b - a for a, b in zip(acks, acks[1:])]
    waves = 1 + sum(1 for g in gaps if g > lat / 4)
    # the tail at several depths, for whoever next has to choose a
    # tail statistic that a bound can hold (PERF.md, section 7)
    tail = {f"p{q}": lats[min(len(lats) - 1, len(lats) * q // 100)]
            for q in (75, 90, 95, 99)}
    return {"acks": len(acks), "waves": waves,
            "largest_ack_gap_s": max(gaps),
            "median_latency_s": lat, "latency_s": tail}


def ack_bins(window, t0: float, seconds: float, width: float = 0.5) -> list:
    bins = [0] * int(seconds / width + 0.999)
    for r in window:
        bins[min(int((r[1] - t0) / width), len(bins) - 1)] += 1
    return bins


