"""The program's sections, read from the profiler's trace.

``ceph_tpu/utils/tracer.py::section`` puts a span on its thread's line
of the same ``.xplane.pb`` as the device's ``XLA Ops``.  ``trace.py``
reduces that file to device time and idle gaps; this module reduces
its host planes to what each layer of the program did with its
threads inside the window:

* per section name: count, seconds, *self* seconds (its duration less
  what its child sections on the same thread cover) and the sums of
  its numeric keywords;
* per thread: the union of its sections, "thread-busy" seconds.  Their
  sum over all threads is the denominator of every ``*_share``.

``ctx`` carries only the reduced trace, but ``<repo>/.bench_trace`` is
still on disk when the readers run (under ``--rehearsal`` too), so the
file is loaded here, once per process.  The plain form keeps each
thread's line apart and each event's keywords, which ``trace.record``
drops: ``{"lines": [[(name, start_ns, dur_ns, {keyword: value}), ...],
...], "device_ops": [(start_ns, end_ns), ...]}``, the second being the
first device's ops (empty on a CPU).

    python3 benchmark/harness/spans.py [trace_dir]

prints the tables PERF.md's section 5 is made from.
"""
import glob
import gzip
import json
import os
import sys

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from harness import spec, trace

TRACE_DIR = os.path.join(spec.ROOT, ".bench_trace")
#: first part of a section's name: the layers of PERF.md, section 3
LAYERS = frozenset((
    "reactor", "msgr", "objecter", "pg", "ec", "batcher", "dispatch",
    "store", "crc", "lock", "timer", "finisher", "sampler", "mon"))

_cache = {}


def is_section(name: str) -> bool:
    """A span the program opened (not JAX's own, not the harness's)."""
    layer, dot, verb = name.partition(".")
    return bool(dot) and layer in LAYERS and "." not in verb \
        and verb.replace("_", "a").isalnum() and name == name.lower()


# -- the plain form ---------------------------------------------------------
def load(log_dir: str = TRACE_DIR) -> dict:
    """The newest .xplane.pb under ``log_dir``: sections and the
    window span by thread line, and the first device's op intervals."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise RuntimeError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(paths[-1])
    lines, device_ops, first_dev = [], [], None
    for plane in data.planes:
        if trace.DEVICE_PLANE.match(plane.name):
            if first_dev is None or plane.name < first_dev:
                first_dev = plane.name
                device_ops = [
                    (float(e.start_ns), float(e.start_ns + e.duration_ns))
                    for ln in plane.lines if ln.name == trace.OPS_LINE
                    for e in ln.events]
            continue
        for ln in plane.lines:
            evs = [(e.name, float(e.start_ns), float(e.duration_ns),
                    dict(e.stats))
                   for e in ln.events
                   if e.name == trace.WINDOW_SPAN or is_section(e.name)]
            if evs:
                lines.append(evs)
    return {"lines": lines, "device_ops": device_ops}


def window_of(plain: dict):
    for evs in plain["lines"]:
        for name, s, d, _ in evs:
            if name == trace.WINDOW_SPAN:
                return s, s + d
    # no window span (an operator's own session): all that was traced
    return trace.find_window({"/host": {
        str(i): [e[:3] for e in evs]
        for i, evs in enumerate(plain["lines"])}})


def record(plain: dict, path: str, seconds: float) -> None:
    """Write the window's first ``seconds`` in the plain form, times
    rebased to the window's start (how tests/recorded_spans.json.gz
    was made)."""
    w0, w1 = window_of(plain)
    w1 = min(w1, w0 + seconds * 1e9)
    out = {"lines": [], "device_ops": [
        [max(s, w0) - w0, min(e, w1) - w0]
        for s, e in plain["device_ops"] if e > w0 and s < w1]}
    for evs in plain["lines"]:
        kept = [[n, max(s, w0) - w0, min(s + d, w1) - max(s, w0), meta]
                for n, s, d, meta in evs
                if n != trace.WINDOW_SPAN and s + d > w0 and s < w1]
        if kept:
            out["lines"].append(kept)
    out["lines"].append([[trace.WINDOW_SPAN, 0.0, w1 - w0, {}]])
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "wt", encoding="utf-8") as fh:
        json.dump(out, fh)


def load_recorded(path: str) -> dict:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt", encoding="utf-8") as fh:
        raw = json.load(fh)
    return {"lines": [[(n, s, d, meta) for n, s, d, meta in evs]
                      for evs in raw["lines"]],
            "device_ops": [tuple(iv) for iv in raw["device_ops"]]}


# -- the reduction ----------------------------------------------------------
def clipped(plain: dict, w0: float, w1: float) -> list:
    """Per thread line its sections inside [w0, w1), outermost first:
    [(start, end, name, keywords), ...]."""
    out = []
    for evs in plain["lines"]:
        kept = sorted(
            ((max(s, w0), min(s + d, w1), n, meta)
             for n, s, d, meta in evs
             if n != trace.WINDOW_SPAN and d > 0 and s + d > w0
             and s < w1),
            key=lambda ev: (ev[0], -ev[1]))
        if kept:
            out.append(kept)
    return out


def self_times(evs: list) -> list:
    """For one thread's nested sections (sorted by start, longest
    first): each one's duration less what its direct children cover."""
    selfs = [e - s for s, e, _, _ in evs]
    stack = []
    for i, (s, e, _, _) in enumerate(evs):
        while stack and evs[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            selfs[stack[-1]] -= min(e, evs[stack[-1]][1]) - s
        stack.append(i)
    return selfs


def reduce(plain: dict, window=None) -> dict:
    """``window`` narrows the reduction to a stretch (start_ns, end_ns)
    of the trace; without one it is the harness's window."""
    w0, w1 = window or window_of(plain)
    names, busy, open_iv, daemons = {}, [], [], []
    for evs in clipped(plain, w0, w1):
        for (s, e, name, meta), own in zip(evs, self_times(evs)):
            row = names.setdefault(name, {"count": 0, "seconds": 0.0,
                                          "self_seconds": 0.0, "sums": {}})
            row["count"] += 1
            row["seconds"] += (e - s) / 1e9
            row["self_seconds"] += own / 1e9
            for key, val in meta.items():
                if isinstance(val, (int, float)) \
                        and not isinstance(val, bool):
                    row["sums"][key] = row["sums"].get(key, 0) + val
        merged = trace._merged((s, e) for s, e, _, _ in evs)
        busy.append(sum(e - s for s, e in merged) / 1e9)
        daemons.append(next((m["d"] for _, _, _, m in evs if "d" in m),
                            ""))
        open_iv.extend(merged)
    return {"window_s": (w1 - w0) / 1e9, "window": (w0, w1),
            "names": names, "thread_busy_s": busy, "thread_d": daemons,
            "busy_s": sum(busy), "open": trace._merged(open_iv)}


def coverage(red: dict, device_ops: list):
    """Share of the device's idle time in the window during which some
    section was open on some thread; None without a device op."""
    w0, w1 = red["window"]
    gaps = trace.gaps_of([(s, e) for s, e in device_ops
                          if e > w0 and s < w1], w0, w1)
    idle = sum(e - s for s, e in gaps)
    if not device_ops or idle <= 0:
        return None
    covered, spans, i = 0.0, red["open"], 0
    for gs, ge in gaps:
        while i < len(spans) and spans[i][1] <= gs:
            i += 1
        j = i
        while j < len(spans) and spans[j][0] < ge:
            covered += min(ge, spans[j][1]) - max(gs, spans[j][0])
            j += 1
    return covered / idle


def breakdown(plain: dict, name: str, keys: tuple, window=None) -> list:
    """Seconds of the sections called ``name`` (a trailing ``*``
    matches a layer) by their keywords ``keys``, most seconds first:
    [(values, count, seconds, self seconds), ...]."""
    w0, w1 = window or window_of(plain)
    out = {}
    for evs in clipped(plain, w0, w1):
        for (s, e, n, meta), own in zip(evs, self_times(evs)):
            if n != name and not (name.endswith("*")
                                  and n.startswith(name[:-1])):
                continue
            row = out.setdefault(tuple(meta.get(k) for k in keys),
                                 [0, 0.0, 0.0])
            row[0] += 1
            row[1] += (e - s) / 1e9
            row[2] += own / 1e9
    return sorted(((k, *v) for k, v in out.items()), key=lambda r: -r[2])


def largest_ack_gap(plain: dict):
    """The longest stretch of the window between two acks (the
    client's ``objecter.reply`` sections): (start_ns, end_ns), or
    None with fewer than two acks."""
    w0, w1 = window_of(plain)
    acks = sorted(s for evs in plain["lines"] for n, s, _, _ in evs
                  if n == "objecter.reply" and w0 <= s < w1)
    if len(acks) < 2:
        return None
    return max(zip(acks, acks[1:]), key=lambda ab: ab[1] - ab[0])


# -- what the readers take --------------------------------------------------
def for_ctx(ctx: dict):
    """-> (plain, reduced) of this run's trace, or (None, None) where
    there is none.  A test hands its own in ``ctx["spans"]``."""
    if "spans" in ctx:
        plain = ctx["spans"]
    else:
        if "plain" not in _cache:
            try:
                _cache["plain"] = load()
            except RuntimeError:
                _cache["plain"] = None
        plain = _cache["plain"]
    if plain is None:
        return None, None
    key = id(plain)
    if _cache.get("reduced_of") != key:
        _cache["reduced"], _cache["reduced_of"] = reduce(plain), key
    return plain, _cache["reduced"]


def share(ctx: dict, layers: tuple, field: str = "self_seconds"):
    """Percent of thread-busy seconds that the sections of ``layers``
    (name prefixes) took; None where the trace holds no section at all
    (a program without them)."""
    _, red = for_ctx(ctx)
    if red is None or red["busy_s"] <= 0:
        return None
    return 100.0 * sum(row[field] for name, row in red["names"].items()
                       if name.startswith(layers)) / red["busy_s"]


def main(argv) -> int:
    plain = load(argv[1] if len(argv) > 1 else TRACE_DIR)
    red = reduce(plain)
    print(f"window {red['window_s']:.3f} s, {len(red['thread_busy_s'])} "
          f"threads with sections, thread-busy {red['busy_s']:.3f} s, "
          f"span coverage {coverage(red, plain['device_ops'])}")
    print(f"{'section':26} {'count':>8} {'seconds':>10} {'self':>10}  sums")
    for name, row in sorted(red["names"].items(),
                            key=lambda kv: -kv[1]["self_seconds"]):
        print(f"{name:26} {row['count']:8d} {row['seconds']:10.4f} "
              f"{row['self_seconds']:10.4f}  {row['sums']}")
    gap = largest_ack_gap(plain)
    if gap is not None:
        inside = reduce(plain, gap)
        print(f"-- largest ack gap: {(gap[1] - gap[0]) / 1e9:.3f} s, "
              f"{(gap[0] - red['window'][0]) / 1e9:.3f} s into the "
              f"window; self seconds inside it, thread-busy "
              f"{inside['busy_s']:.3f} s")
        for name, row in sorted(inside["names"].items(),
                                key=lambda kv: -kv[1]["self_seconds"])[:8]:
            print(f"   {name:26} {row['count']:7d} "
                  f"{row['self_seconds']:9.4f}")
        for vals, n, secs, _ in breakdown(plain, "lock.wait",
                                          ("site", "holder"), gap)[:5]:
            print(f"   lock.wait {str(vals):50} {n:5d} {secs:9.4f}")
    for name, keys in (("lock.wait", ("site",)),
                       ("lock.wait", ("site", "holder")),
                       ("reactor.*", ("fn",)), ("timer.cb", ("fn",)),
                       ("finisher.cb", ("fn",)),
                       ("dispatch.call", ("kernel",)),
                       ("batcher.dispatch", ("lane",)),
                       ("msgr.dispatch", ("type",))):
        print(f"-- {name} by {', '.join(keys)}")
        for vals, n, secs, own in breakdown(plain, name, keys)[:12]:
            print(f"   {str(vals):70} {n:7d} {secs:9.4f} {own:9.4f}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
