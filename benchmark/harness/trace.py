"""The benchmark's own trace: a jax.profiler session around the window,
and the reduction from its events to device busy and idle time, time
per program family, and what the host was doing in the idle gaps.

The reduction works on a plain form of the trace, ``{plane: {line:
[(name, start_ns, dur_ns), ...]}}``, so that it can be checked on a
small recorded trace (``tests/recorded_trace.json``) without a chip.
"""
import bisect
import glob
import gzip
import json
import os
import re
import shutil

WINDOW_SPAN = "bench.window"
CLIENT_SPANS = ("client.wait", "client.submit")
UNSPANNED = "unspanned"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


# -- the session ----------------------------------------------------------
def start(log_dir: str) -> None:
    import jax
    shutil.rmtree(log_dir, ignore_errors=True)
    os.makedirs(log_dir, exist_ok=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0        # ~80 threads of Python: too dear
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def stop() -> None:
    import jax
    jax.profiler.stop_trace()


def load(log_dir: str) -> dict:
    """The newest .xplane.pb under ``log_dir`` in the plain form."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise RuntimeError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(paths[-1])
    planes = {}
    for plane in data.planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            evs = lines.setdefault(line.name, [])
            for e in line.events:
                evs.append((e.name, float(e.start_ns), float(e.duration_ns)))
    return planes


def record(planes: dict, path: str, seconds: float) -> None:
    """Write the events of the window's first ``seconds`` in the plain
    form, times rebased to the window's start."""
    w0, w1 = find_window(planes)
    w1 = min(w1, w0 + seconds * 1e9)
    out = {}
    for pname, lines in planes.items():
        for lname, evs in lines.items():
            kept = [[n, s - w0, e - s] for n, s, e in _clip(evs, w0, w1)
                    if n != WINDOW_SPAN]
            if kept:
                out.setdefault(pname, {})[lname] = kept
    out.setdefault("/host:bench", {})["window"] = [[WINDOW_SPAN, 0.0,
                                                    w1 - w0]]
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "wt", encoding="utf-8") as fh:
        json.dump(out, fh)


def load_recorded(path: str) -> dict:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt", encoding="utf-8") as fh:
        raw = json.load(fh)
    return {p: {ln: [tuple(e) for e in evs] for ln, evs in lines.items()}
            for p, lines in raw.items()}


# -- the reduction ----------------------------------------------------------
def union_seconds(intervals) -> float:
    """Length of the union of (start_ns, end_ns) intervals, in seconds."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total / 1e9


def gaps_of(intervals, w0: float, w1: float) -> list:
    """The idle (start, end) stretches of [w0, w1) left by intervals."""
    out, at = [], w0
    for s, e in sorted(intervals):
        if s > at:
            out.append((at, min(s, w1)))
        at = max(at, e)
        if at >= w1:
            break
    if at < w1:
        out.append((at, w1))
    return [(s, e) for s, e in out if e > s]


def find_window(planes: dict):
    """(start_ns, end_ns) of the span the harness put around the window;
    without one, the extent of all events."""
    for pname, lines in planes.items():
        if DEVICE_PLANE.match(pname):
            continue
        for evs in lines.values():
            for name, s, d in evs:
                if name == WINDOW_SPAN:
                    return s, s + d
    lo = min((s for ls in planes.values() for evs in ls.values()
              for _, s, _ in evs), default=0.0)
    hi = max((s + d for ls in planes.values() for evs in ls.values()
              for _, s, d in evs), default=0.0)
    return lo, hi


def _clip(evs, w0, w1):
    out = []
    for name, s, d in evs:
        e = s + d
        if e <= w0 or s >= w1 or d <= 0:
            continue
        out.append((name, max(s, w0), min(e, w1)))
    return out


def device_planes(planes: dict) -> list:
    return sorted(p for p in planes if DEVICE_PLANE.match(p))


def family_of(module_name: str, families: list):
    for fam in families:
        for pat in fam["module_patterns"]:
            if re.search(pat, module_name):
                return fam
    return None


def reduce(planes: dict, families: list) -> dict:
    """Everything the per-layer readers and the result line take from
    a trace.  Raises where the trace has no device plane or no device
    operation in the window: that is a malformed run, not a zero."""
    devs = device_planes(planes)
    if not devs:
        raise RuntimeError("the trace has no /device:TPU:<n> plane: "
                           "not a chip run")
    w0, w1 = find_window(planes)
    window_s = (w1 - w0) / 1e9
    busy, per_dev_ops = [], {}
    for p in devs:
        ops = _clip(planes[p].get(OPS_LINE, []), w0, w1)
        per_dev_ops[p] = ops
        busy.append(union_seconds((s, e) for _, s, e in ops))
    busy_s = sum(busy) / len(busy)
    if busy_s <= 0:
        raise RuntimeError("no operation ran on the device inside the "
                           "traced window")

    # seconds per program family, by the XLA module an op ran inside
    fam_s, fam_mods, unmatched = {}, {}, {}
    op_s = {}
    for p in devs:
        mods = sorted(_clip(planes[p].get(MODULES_LINE, []), w0, w1),
                      key=lambda m: m[1])
        starts = [m[1] for m in mods]
        for name, s, e in per_dev_ops[p]:
            op_s[name] = op_s.get(name, 0.0) + (e - s) / 1e9
            i = bisect.bisect_right(starts, s) - 1
            mod = mods[i][0] if i >= 0 and mods[i][2] >= s else "(no module)"
            fam = family_of(mod, families)
            key = fam["family"] if fam else None
            if key is None:
                unmatched[mod] = unmatched.get(mod, 0.0) + (e - s) / 1e9
                continue
            fam_s[key] = fam_s.get(key, 0.0) + (e - s) / 1e9
            fam_mods.setdefault(key, set()).add(mod)
    n = len(devs)
    fam_s = {k: v / n for k, v in fam_s.items()}

    # idle gaps of the first device, by what the host was doing
    host = []
    for pname, lines in planes.items():
        if DEVICE_PLANE.match(pname):
            continue
        for lname, evs in lines.items():
            for name, s, e in _clip(evs, w0, w1):
                if name != WINDOW_SPAN:
                    host.append((s, e, name, lname))
    gaps = gaps_of(((s, e) for _, s, e in per_dev_ops[devs[0]]), w0, w1)
    idle = attribute_gaps(gaps, host)
    top_ops = sorted(op_s.items(), key=lambda kv: -kv[1])[:10]
    shape = {p: {ln: len(evs) for ln, evs in lines.items()}
             for p, lines in planes.items()}
    return {"window_s": window_s, "busy_s": busy_s, "planes": shape,
            "family_seconds": fam_s,
            "family_modules": {k: sorted(v) for k, v in fam_mods.items()},
            "unmatched_module_seconds": unmatched,
            "device_ops": [[k, v] for k, v in top_ops],
            "idle_gaps": idle,
            "largest_gap_s": max(((e - s) / 1e9 for s, e in gaps),
                                 default=0.0),
            "n_device_ops": sum(len(v) for v in per_dev_ops.values())}


def _merged(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def attribute_gaps(gaps: list, host: list) -> list:
    """Each gap goes, whole, to the shortest host span that covers its
    middle.  A span of the load generator's own (a caller waiting for
    its reply) explains nothing: a gap that only such a span covers,
    or none, is ``unspanned``.  -> the ten names with the most idle
    seconds, [[name, seconds], ...]."""
    other = sorted(h for h in host if h[2] not in CLIENT_SPANS)
    starts = [h[0] for h in other]
    # the longest span bounds how far back a covering span can start
    longest = max((e - s for s, e, _, _ in other), default=0.0)
    out = {}
    for gs, ge in gaps:
        mid = (gs + ge) / 2
        hi = bisect.bisect_right(starts, mid)
        lo = bisect.bisect_left(starts, mid - longest)
        best = None
        for s, e, name, lname in other[lo:hi]:
            if e >= mid and (best is None or e - s < best[0]):
                best = (e - s, name)
        key = best[1] if best is not None else UNSPANNED
        out[key] = out.get(key, 0.0) + (ge - gs) / 1e9
    return [[k, v] for k, v in
            sorted(out.items(), key=lambda kv: -kv[1])[:10]]
