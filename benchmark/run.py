#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process boots the cell's deployment (mon and OSDs as threads, the
chip owned by this process), fills the pipeline, runs a warm-up load
that is part of set-up, cuts the window [t0, t0+seconds) out of the
continuing load, drains, reads the device's peak memory, checks what
the window wrote and read against the plain reference, and prints one
JSON line last.  ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics from counters, hop ledgers and a
jax.profiler trace of the window.

``--rehearsal`` is for a machine without a chip: the same code at a
tiny size, a last line marked as not a chip run, no device metric.
Without it the command fails unless jax.default_backend() is "tpu".
"""
import argparse
import gc
import json
import os
import sys
import threading
import time

T_IMPORT = time.monotonic()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)


def process_age() -> float:
    """Seconds since this process started (the kernel's stamp)."""
    try:
        with open("/proc/self/stat") as fh:
            start_ticks = int(fh.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as fh:
            uptime = float(fh.read().split()[0])
        return uptime - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.monotonic() - T_IMPORT


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class Phases:
    """setup_s split by phase: seconds from process start, by mark."""

    def __init__(self):
        self.at = process_age()
        self.mono = time.monotonic()
        self.rows = [("interpreter_start", self.at)]

    def mark(self, name: str, mono: float = None) -> None:
        now = self.seconds_at(time.monotonic() if mono is None else mono)
        spent = now - sum(s for _, s in self.rows)
        self.rows.append((name, spent))
        log(f"setup: {name} {spent:.2f}s")

    def seconds_at(self, mono: float) -> float:
        return self.at + (mono - self.mono)


class CompileCount:
    """Programs JAX lowered and compiled, from its own monitoring
    events; read at the window's edges."""

    def __init__(self):
        import jax.monitoring
        self.lowered = 0
        self.compiled = 0
        self.last = time.monotonic()
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event.endswith("jaxpr_to_mlir_module_duration"):
            self.lowered += 1
            self.last = time.monotonic()
        elif event.endswith("backend_compile_duration"):
            self.compiled += 1
            self.last = time.monotonic()


def shrink_for_rehearsal(cell) -> None:
    """A tiny size for a machine with no chip: every code path, no
    number that means anything."""
    t = cell.traffic
    small = 256 << 10
    for op in t["ops"]:
        if op["io_bytes"] == t["object_bytes"]:
            op["io_bytes"] = small
    t["object_bytes"] = small
    t["populate_objects"] = min(t.get("populate_objects", 0), 8)
    t["payload_pool"] = 8
    t["patch_pool"] = min(t.get("patch_pool", 0), 64)
    t["warmup_seconds"] = 1
    t["warmup_quiet_seconds"] = 0.5
    t["settle_seconds"] = 0.5
    chk = t.setdefault("check", {})
    chk["parity_objects"] = min(chk.get("parity_objects", 8), 4)
    if chk.get("keep_every"):
        chk["keep_every"] = 2


def device_info(chips: int, rehearsal: bool) -> dict:
    import jax
    backend = jax.default_backend()
    devs = jax.devices()
    if not rehearsal:
        if backend != "tpu":
            raise SystemExit(f"benchmark: needs a TPU, jax.default_backend()"
                             f" is {backend!r} (use --rehearsal on a CPU)")
        if len(devs) < chips:
            raise SystemExit(f"benchmark: the cell asks for {chips} chips,"
                             f" JAX sees {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak(rehearsal: bool) -> int:
    import jax
    peak = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    if peak <= 0 and not rehearsal:
        raise RuntimeError("the device reports no peak_bytes_in_use")
    return peak


def set_up(dep, cell, model, phases, plant) -> None:
    """Boot, pool, prewarm, the populated set, the cell's own state."""
    from harness import check
    dep.boot()
    phases.mark("cluster_boot")
    dep.make_pool()
    phases.mark("pool_peering")
    dep.wait_prewarm()
    phases.mark("prewarm_compile")
    check.populate(dep.io, model, model.n_populated)
    for step in cell.state.get("after_populate", []):
        if "kill_osd_with_data" not in step:
            raise ValueError(f"unknown cell step {step}")
        victim = dep.kill_osd_with_data(step["kill_osd_with_data"])
        log(f"setup: osd.{victim} killed with its data, down and in")
    phases.mark("populate")
    if plant is not None:
        plant(dep)


def warm_up(dep, cell, compiles) -> int:
    """The load is running: let it run until every shape it dispatches
    is compiled, then run the cached GF programs at the batch sizes it
    has not formed yet; -> how many such calls."""
    t_warm = time.monotonic()
    time.sleep(float(cell.traffic.get("warmup_seconds", 5)))
    quiet = float(cell.traffic.get("warmup_quiet_seconds", 2))
    while time.monotonic() - compiles.last < quiet and \
            time.monotonic() - t_warm < 120:
        time.sleep(0.25)                # a shape is still compiling
    dep.wait_prewarm()
    try:
        return dep.warm_cached_programs(cell.traffic)
    except (AttributeError, ImportError, TypeError) as e:
        log(f"setup: cached programs not warmed at their batch sizes "
            f"({type(e).__name__}: {e})")
        return 0


def cut_window(dep, gen, seconds: float, compiles, settle: float,
               trace_dir) -> dict:
    """Cut [t0, t0+seconds) out of the running load; -> what was read
    at its edges.  Between the two edges the harness only sleeps."""
    from harness import study, trace
    # the harness's own process settings (PERF.md, section 2): the
    # daemons' long-lived heaps leave the cyclic collector's sight
    gc.collect()
    gc.freeze()
    w = {"gc": study.GcClock(), "snap_a": dep.snapshot()}
    if trace_dir:
        trace.start(trace_dir)
    # collecting, dumping and starting the profiler stalled every
    # thread for a moment: let the pipeline run level again
    time.sleep(settle)
    edge = threading.Event()
    w["lanes_a"] = dep.lane_counts()
    w["lowered_a"] = compiles.lowered
    t0 = w["t0"] = gen.t0 = time.monotonic()
    with gen.span(trace.WINDOW_SPAN):
        while True:
            left = t0 + seconds - time.monotonic()
            if left <= 0:
                break
            edge.wait(left)
    w["lanes_b"] = dep.lane_counts()
    w["lowered_b"] = compiles.lowered
    # closed; the load runs on, and drains untimed
    if trace_dir:
        trace.stop()
    w["snap_b"] = dep.snapshot()
    time.sleep(0.25)
    gen.stop_and_drain()
    return w


def read_trace(args, trace_dir: str, device: dict):
    """-> (the reduced trace, the result's ``breakdown``)."""
    from harness import spec, trace
    planes = trace.load(trace_dir)
    if args.record_trace:
        trace.record(planes, args.record_trace, 1.5)
    red = trace.reduce(planes, spec.kernel_families())
    device["busy_s"] = red["busy_s"]
    device["window_s"] = red["window_s"]
    return red, {"device_ops": red["device_ops"],
                 "idle_gaps": red["idle_gaps"]}


def read_metrics(cell, args, ctx: dict) -> dict:
    from harness import spec
    metrics = {}
    for m in (cell.per_layer() if args.trace else cell.end_to_end()):
        reader = spec.metric_reader(m["name"])
        if args.rehearsal and reader.SOURCE == "device_trace":
            continue                    # never a device number from a CPU
        value = reader.read(ctx)
        if value is None:
            continue                    # nothing to read: left out
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return metrics


def run_cell(args, plant=None, bench=None) -> dict:
    """One run; -> the result object.  ``plant(dep)`` is the control's
    and the fault tests' hook: it breaks the timed path once the set
    is populated, before the load starts.  ``bench`` is a benchmark
    description to take the cell from in place of BENCHMARK.json (a
    test's rehearsal of a deployment that has no cell yet)."""
    from harness import check, deploy, loadgen, seeded, spec, study
    phases = Phases()
    cell = spec.Cell(args.workload, bench=bench)
    if args.rehearsal:
        shrink_for_rehearsal(cell)
    seconds = float(args.seconds)

    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    import jax
    device = device_info(cell.chips, args.rehearsal)
    from ceph_tpu.utils import compile_cache, nativebuild
    cache_dir = compile_cache.configure()
    compiles = CompileCount()
    jax.devices()[0].memory_stats()     # the runtime is up
    phases.mark("jax_start")
    nativebuild.load("gf_native.cc", "libceph_tpu_gf")
    nativebuild.load("crc32c.cc", "libceph_tpu_crc32c")
    phases.mark("native_build")

    probe_s = study.host_speed_probe()
    model = seeded.ByteModel(args.seed, cell.traffic)
    dep = deploy.Deployment(cell.config)
    trace_dir = os.path.join(ROOT, ".bench_trace") if args.trace else None
    try:
        set_up(dep, cell, model, phases, plant)
        gen = loadgen.LoadGen(dep.io, model, cell.traffic,
                              annotate=bool(args.trace))
        gen.start()
        warmed = warm_up(dep, cell, compiles)
        w = cut_window(dep, gen, seconds, compiles,
                       float(cell.traffic.get("settle_seconds", 3)),
                       trace_dir)
        t0 = w["t0"]
        phases.mark("warmup_load", t0)
        device["memory_peak_bytes"] = memory_peak(args.rehearsal)
        window = gen.in_window(t0, seconds)
        lanes_window = deploy.diff(w["lanes_b"], w["lanes_a"])

        t_chk = time.monotonic()
        numbers, checked = check.run(dep, cell, model, gen, t0, seconds,
                                     args.seed, lanes_window)
        check_s = time.monotonic() - t_chk
    finally:
        dep.stop()

    ctx = {"cell": cell, "seconds": seconds, "window": window,
           "ops": gen.ops, "k": dep.k, "m": dep.m,
           "stripe_unit": dep.stripe_unit, "lost": len(dep.dead),
           "snap": deploy.diff(w["snap_b"], w["snap_a"]),
           "lanes_window": lanes_window,
           "compiles_in_window": w["lowered_b"] - w["lowered_a"],
           "setup_s": phases.seconds_at(t0), "device": device,
           "trace": None}
    breakdown = None
    if args.trace and not args.rehearsal:
        ctx["trace"], breakdown = read_trace(args, trace_dir, device)
    result = {"correct": check.correct(numbers) and not gen.errors,
              "attempted": len(window),
              "failed": sum(1 for r in window
                            if not loadgen.op_ok(r, gen.ops)),
              "metrics": read_metrics(cell, args, ctx), "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if args.rehearsal:
        result["rehearsal"] = True
        result["not_a_chip_run"] = "CPU rehearsal at a tiny size: " \
            "no number here is a measurement"
    info = {"workload": cell.name, "seed": args.seed,
            "seconds": seconds, "trace": int(args.trace),
            "setup_phases_s": dict(phases.rows),
            "compile_cache": cache_dir,
            "host_speed_probe_s": probe_s,
            "programs_warmed_at_batch_sizes": warmed,
            "programs_lowered_total": compiles.lowered,
            "programs_compiled_total": compiles.compiled,
            "reference_check_s": check_s, "checked": checked,
            "lanes_window": lanes_window,
            "osdmap_epochs": [w["lanes_a"]["osdmap_epoch"],
                              w["lanes_b"]["osdmap_epoch"]],
            "study": study.ack_study(window),
            "acks_per_half_second": study.ack_bins(window, t0, seconds),
            "gc_in_window": w["gc"].in_window(t0, seconds),
            "generator_errors": gen.errors[:4]}
    if ctx["trace"] is not None:
        info["trace"] = {k: ctx["trace"][k] for k in (
            "family_seconds", "family_modules", "unmatched_module_seconds",
            "largest_gap_s", "n_device_ops", "planes")}
    # the numbers compared, each beside its limit: last in the line
    result["compared"] = {k: {"value": v, "limit": lim}
                          for k, (v, lim) in numbers.items()}
    result["_info"] = info
    return result


def emit(result: dict) -> None:
    info = result.pop("_info")
    print(json.dumps({"info": info}), flush=True)
    for name, row in result["compared"].items():
        log(f"compared: {name} = {row['value']} (limit {row['limit']})")
    log(f"correct: {result['correct']}")
    print(json.dumps(result), flush=True)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="CPU, tiny size, last line marked not a chip run")
    ap.add_argument("--record-trace", default=None, metavar="PATH",
                    help="with --trace 1: also write the plain form of "
                         "the window's first 1.5 s of trace there (how "
                         "tests/recorded_trace.json was made)")
    return ap.parse_args(argv)


def refusal_code(e: SystemExit) -> int:
    """A refusal (no chip, an unknown name, a pool that is not the
    file's) prints its message and no result line, and its code is not
    0; the cluster's threads, stopped or not, do not hold the exit."""
    if isinstance(e.code, int) or e.code is None:
        return e.code or 0
    log(str(e.code))
    return 1


def main(argv=None) -> int:
    args = parse(argv)
    if args.rehearsal:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    result = run_cell(args)
    emit(result)
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except SystemExit as e:
        code = refusal_code(e)
    except BaseException:
        import traceback
        traceback.print_exc()
        code = 1
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)      # daemon threads of a stopped cluster must not hold the exit
