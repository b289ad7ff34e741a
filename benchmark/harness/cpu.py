"""The interpreter's budget by layer, read from the program's sections.

Inside a profiler session ``ceph_tpu/utils/tracer.py::section`` probes
one nest in ``1 / PROBE_SHARE``, drawn at each thread's outermost
section: every section of a probed nest carries ``cpu_ns``, its
thread's CPU time inside it; the other nests carry none.  A section's
wall time less ``cpu_ns`` is time off the CPU with work in hand
(waiting for the interpreter lock, or a blocking call).  This module
reduces them as ``spans.reduce`` reduces wall seconds:

* per section name: count, how many were probed, wall seconds, CPU
  seconds and *self* CPU seconds (its ``cpu_ns`` less its direct
  children's on the same thread), the last two over probed sections;
* the whole: thread-busy seconds (per thread the union of its
  sections, as ``spans.reduce`` counts them), the wall and the CPU of
  the probed nests.  Shares are taken within the probed nests; a total
  is their CPU scaled by thread-busy over their wall.

A section cut by the window's edge counts its ``cpu_ns`` in proportion
to the part of its wall time inside.  A trace on which no section
carries ``cpu_ns`` (a program before this keyword) reduces to ``None``,
and every reader then reads ``None``.

    python3 benchmark/harness/cpu.py [trace_dir]

prints per section name: count, probed, wall s, CPU s, self CPU s and
ms of CPU a probed section; then the same inside the largest ack gap,
with its threads: a native call that holds the interpreter reads CPU
near wall on one thread, a stopped process CPU near 0 on every probed
thread.
"""
import os
import sys

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from harness import spans, trace

#: ``<layer>.cpu_share`` -> the first parts of its sections' names, the
#: groups of the ``*.self_share`` metrics
LAYERS = {"msgr": ("msgr.",), "crc": ("crc.",), "store": ("store.",),
          "batcher": ("batcher.", "dispatch."), "pg": ("pg.", "ec.")}

_cache = {}


def _pro_rata(plain: dict, w0: float, w1: float) -> dict:
    """``plain`` with each section that a window edge cuts carrying the
    part of its ``cpu_ns`` that its wall inside [w0, w1) is of its
    wall (what ``spans.clipped`` then keeps of it)."""
    lines = []
    for evs in plain["lines"]:
        line = []
        for name, s, d, meta in evs:
            if (s < w0 or s + d > w1) and d > 0 and "cpu_ns" in meta:
                inside = min(s + d, w1) - max(s, w0)
                meta = dict(meta, cpu_ns=meta["cpu_ns"] * max(inside, 0) / d)
            line.append((name, s, d, meta))
        lines.append(line)
    return {"lines": lines}


def _parents(evs: list) -> list:
    """For one thread's nested sections (``spans.clipped`` order): the
    index of each one's direct parent, or None for an outermost one.
    The stack walk of ``spans.self_times``, which returns wall alone."""
    out, stack = [], []
    for i, (s, _, _, _) in enumerate(evs):
        while stack and evs[stack[-1]][1] <= s:
            stack.pop()
        out.append(stack[-1] if stack else None)
        stack.append(i)
    return out


def reduce(plain: dict, window=None):
    """``window`` narrows the reduction to a stretch (start_ns, end_ns);
    without one it is the harness's window.  ``None`` where no section
    in it carries ``cpu_ns``."""
    w0, w1 = window or spans.window_of(plain)
    names, threads = {}, []
    for evs in spans.clipped(_pro_rata(plain, w0, w1), w0, w1):
        cpu = [m.get("cpu_ns") for _, _, _, m in evs]
        parents = _parents(evs)
        own = list(cpu)
        for c, p in zip(cpu, parents):
            if c is not None and p is not None and own[p] is not None:
                own[p] -= c
        probed_ns = cpu_ns = 0.0
        for (s, e, name, _), c, p, mine in zip(evs, cpu, parents, own):
            row = names.setdefault(name, {
                "count": 0, "probed": 0, "seconds": 0.0, "cpu_s": 0.0,
                "self_cpu_s": 0.0})
            row["count"] += 1
            row["seconds"] += (e - s) / 1e9
            if c is None:
                continue
            row["probed"] += 1
            row["cpu_s"] += c / 1e9
            row["self_cpu_s"] += mine / 1e9
            if p is None or cpu[p] is None:     # the top of a probed nest
                probed_ns += e - s
                cpu_ns += c
        threads.append({
            "d": next((m["d"] for *_, m in evs if "d" in m), ""),
            "busy_s": trace.union_seconds((s, e) for s, e, _, _ in evs),
            "probed_s": probed_ns / 1e9, "cpu_s": cpu_ns / 1e9})
    probed_s = sum(t["probed_s"] for t in threads)
    if probed_s <= 0:
        return None
    return {"window_s": (w1 - w0) / 1e9, "window": (w0, w1),
            "names": names, "threads": threads,
            "busy_s": sum(t["busy_s"] for t in threads),
            "probed_s": probed_s,
            "cpu_s": sum(t["cpu_s"] for t in threads)}


def all_cpu_s(red: dict) -> float:
    """The CPU seconds of every section, probed or not: the probed
    nests' CPU over their wall, times thread-busy seconds."""
    return red["cpu_s"] * red["busy_s"] / red["probed_s"]


# -- what the readers take --------------------------------------------------
def for_ctx(ctx: dict):
    """The reduction of this run's trace (or of a test's, handed in
    ``ctx["spans"]``), or None.  Kept beside the trace it was made of,
    which the cache holds, so a new trace is never read as an old one."""
    plain, _ = spans.for_ctx(ctx)
    if plain is None:
        return None
    if _cache.get("plain") is not plain:
        _cache["plain"], _cache["reduced"] = plain, reduce(plain)
    return _cache["reduced"]


def ms_per_op(ctx: dict):
    """The sections' CPU in ms over the ops acknowledged in full in the
    window."""
    from harness.loadgen import op_ok
    red = for_ctx(ctx)
    if red is None:
        return None
    acked = sum(1 for r in ctx["window"] if op_ok(r, ctx["ops"]))
    if acked <= 0:
        return None
    return all_cpu_s(red) * 1e3 / acked


def offcpu_share(ctx: dict):
    """Percent of the probed nests' wall in which their thread was not
    on the CPU."""
    red = for_ctx(ctx)
    if red is None:
        return None
    return 100.0 * (1.0 - red["cpu_s"] / red["probed_s"])


def share(ctx: dict, layer: str):
    """Percent of the probed nests' CPU that the self CPU of
    ``layer``'s sections (``LAYERS``) took."""
    red = for_ctx(ctx)
    if red is None or red["cpu_s"] <= 0:
        return None
    own = sum(row["self_cpu_s"] for name, row in red["names"].items()
              if name.startswith(LAYERS[layer]))
    return 100.0 * own / red["cpu_s"]


# -- the printer ------------------------------------------------------------
def _table(red: dict, top: int = None) -> None:
    print(f"   {'section':26} {'count':>8} {'probed':>7} {'wall s':>10} "
          f"{'cpu s':>10} {'self cpu':>10} {'cpu ms each':>11}")
    rows = sorted(red["names"].items(),
                  key=lambda kv: (-kv[1]["self_cpu_s"], -kv[1]["seconds"]))
    for name, row in rows[:top]:
        each = 1e3 * row["cpu_s"] / row["probed"] if row["probed"] else 0.0
        print(f"   {name:26} {row['count']:8d} {row['probed']:7d} "
              f"{row['seconds']:10.4f} {row['cpu_s']:10.4f} "
              f"{row['self_cpu_s']:10.4f} {each:11.4f}")


def _summary(red: dict) -> str:
    off = 100 * (1 - red["cpu_s"] / red["probed_s"])
    return (f"{red['window_s']:.3f} s, thread-busy {red['busy_s']:.3f} s "
            f"({red['busy_s'] / red['window_s']:.2f} threads), probed "
            f"{red['probed_s']:.3f} s of it with {red['cpu_s']:.3f} s of "
            f"CPU, all sections {all_cpu_s(red) / red['window_s']:.3f} "
            f"cores, off the CPU {off:.2f} % of the probed wall")


def main(argv) -> int:
    plain = spans.load(argv[1] if len(argv) > 1 else spans.TRACE_DIR)
    red = reduce(plain)
    if red is None:
        print("no section carries cpu_ns: a program before this keyword")
        return 1
    print(f"window {_summary(red)}")
    _table(red)
    print("-- self CPU by layer, % of the probed nests' CPU")
    for layer, prefixes in LAYERS.items():
        own = sum(r["self_cpu_s"] for n, r in red["names"].items()
                  if n.startswith(prefixes))
        print(f"   {layer:10} {100 * own / red['cpu_s']:8.2f}")
    gap = spans.largest_ack_gap(plain)
    inside = reduce(plain, gap) if gap is not None else None
    if inside is not None:
        print(f"-- largest ack gap, {(gap[0] - red['window'][0]) / 1e9:.3f} s "
              f"into the window: {_summary(inside)}")
        _table(inside, 16)
        print("   threads by CPU inside it (daemon, busy s, probed s, cpu s)")
        for t in sorted(inside["threads"], key=lambda t: -t["cpu_s"])[:12]:
            print(f"   {t['d']:26} {t['busy_s']:10.4f} {t['probed_s']:10.4f} "
                  f"{t['cpu_s']:10.4f}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
