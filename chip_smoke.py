#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served EC path runs on the chip.

    python3 chip_smoke.py [--seed N]

One process (the cluster's daemons are threads of this interpreter, and
it owns the chip or chips):

  phase 0  device    fails unless jax.default_backend() == "tpu"
  phase 1  codec     plugin=tpu against plugin=jerasure (which shares no
                     kernel with the device path) at BASELINE.json's own
                     widths, bit-exact, naming the kernel that served
  phase 2  cluster   13 OSDs on bluestore, pool plugin=tpu reed_sol_van
                     k=8 m=4, default 4 KiB stripe unit, upstream's
                     `rados bench` shape (4 MiB objects, 16 in flight),
                     ec_tpu_fallback_cpu=false so every group on the
                     encode, decode and delta lanes goes to the device:
                     write, read back, sub-stripe overwrites, OSD loss
                     with degraded reads and rebuild, deep scrub — every
                     read bit-exact — then the counters, read over the
                     admin-command path, must show the device did the
                     work and nothing fell back

There is no CPU mode and nothing here is best-effort: any failure ends
the run with its traceback and a non-zero exit code.  On success the
last line of stdout is
    {"ok": true, "device": {"platform": ..., "kind": ..., "count": N}}
The smoke measures nothing against the CPU twin and pins no routing;
the times it prints are observations, not benchmark results.
"""
import argparse
import json
import os
import sys
import time
from collections import deque

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

RS_K8M4 = {"technique": "reed_sol_van", "k": "8", "m": "4", "w": "8"}
#: phase 1: (name, profile, stripes, stripe bytes, erasures)
CODEC_GEOMETRIES = (
    ("rs_k8m4_32KiB_stripes", RS_K8M4, 1024, 32 << 10, (1, 5, 9, 11)),
    ("rs_k8m4_1MiB_stripes", RS_K8M4, 64, 1 << 20, (1, 5, 9, 11)),
    ("rs_k8m4_4MiB_stripes", RS_K8M4, 8, 4 << 20, (1, 5, 9, 11)),
    ("cauchy_good_k10m4_4MiB_stripes",
     {"technique": "cauchy_good", "k": "10", "m": "4"},
     8, 4 << 20, (1, 5)),
    ("rs_k4m2_w16_256KiB_stripes",
     {"technique": "reed_sol_van", "k": "4", "m": "2", "w": "16"},
     64, 256 << 10, (0, 5)),
)
#: the kernel README "TPU-first design notes" promises each codec
#: family on a TPU; anything else there is a silent switch
TPU_KERNEL = {"reed_sol_van/8": "gf_mxu_pallas",
              "cauchy_good/8": "packet_mxu_pallas",
              "reed_sol_van/16": "bitplane_xla"}
#: batch buckets the OSD batcher dispatches for 4 MiB objects at the
#: 4 KiB stripe unit (128 stripes each, tiled at ec_tpu_batch_stripes)
SERVED_BATCHES = (1024, 512, 256, 128)


def say(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------
# phase 0
# ---------------------------------------------------------------------
def phase_device(require_tpu: bool = True) -> dict:
    from importlib import metadata

    import jax
    import jaxlib
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "absent"
    backend = jax.default_backend()
    if require_tpu and backend != "tpu":
        raise RuntimeError(
            f"chip_smoke needs a TPU: jax.default_backend() is "
            f"{backend!r}")
    from ceph_tpu.utils import compile_cache
    cache = compile_cache.configure()
    devs = jax.devices()
    device = {"platform": devs[0].platform,
              "kind": devs[0].device_kind, "count": len(devs)}
    say(f"phase 0: platform={device['platform']} "
        f"device_kind={device['kind']} count={device['count']} "
        f"jax={jax.__version__} jaxlib={jaxlib.__version__} "
        f"libtpu={libtpu} compile_cache={cache}")
    return device


# ---------------------------------------------------------------------
# phase 1
# ---------------------------------------------------------------------
def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def _first_call(backend, fn):
    """Run ``fn``; -> (result, seconds, names of the kernels it
    dispatched)."""
    before = dict(backend.kernel_calls)
    out, seconds = _timed(fn)
    after = backend.kernel_calls
    return out, seconds, sorted(
        n for n in after if after[n] > before.get(n, 0))


def _check_mesh(handle, n_devices: int, what: str) -> None:
    if n_devices > 1 and len(handle.device_ids) != n_devices:
        raise AssertionError(
            f"{what}: output laid out on devices {handle.device_ids}, "
            f"expected all {n_devices}")


def phase_codec(seed: int, geometries=CODEC_GEOMETRIES,
                served_batches=SERVED_BATCHES) -> dict:
    """Every geometry through the plugin registry, bit-exact against
    the jerasure plugin; then the k=8 m=4 shapes the OSD batcher
    dispatches, through the async entry points it calls, which also
    leaves them compiled in the shared in-process caches before phase 2
    boots thirteen daemons."""
    import jax

    from ceph_tpu.ec import registry as ecreg
    on_tpu = jax.default_backend() == "tpu"
    n_devices = len(jax.devices())
    reg = ecreg.instance()
    rng = np.random.default_rng(seed)
    report = {}
    for name, profile, stripes, stripe_bytes, erasures in geometries:
        tpu = reg.factory("tpu", dict(profile))
        ref = reg.factory("jerasure", dict(profile))
        backend = tpu.core.backend
        k, m = tpu.k, tpu.m
        L = tpu.get_chunk_size(stripe_bytes)
        data = rng.integers(0, 256, (stripes, k, L), dtype=np.uint8)
        want = ref.core.encode_batch(data)
        parity, cold_s, kernels = _first_call(
            backend, lambda: tpu.encode_batch(data))
        _, warm_s = _timed(lambda: tpu.encode_batch(data))
        if not np.array_equal(parity, want):
            raise AssertionError(f"{name}: encode differs from jerasure")
        full = np.concatenate([data, want], axis=1)
        present = {i: full[:, i] for i in range(k + m)
                   if i not in erasures}
        dec, dcold_s, dkernels = _first_call(
            backend, lambda: tpu.decode_batch(present, L))
        _, dwarm_s = _timed(lambda: tpu.decode_batch(present, L))
        for e in erasures:
            if not np.array_equal(dec[e], full[:, e]):
                raise AssertionError(f"{name}: decode of chunk {e} "
                                     f"differs from the source")
        family = f"{profile['technique']}/{tpu.w}"
        if on_tpu and (kernels != [TPU_KERNEL[family]]
                       or dkernels != [TPU_KERNEL[family]]):
            raise AssertionError(
                f"{name}: served by encode={kernels} decode={dkernels}"
                f", README promises {TPU_KERNEL[family]} on TPU")
        report[name] = {
            "shape": [stripes, k, L], "kernel": kernels,
            "decode_kernel": dkernels,
            "reference_backend": ref.core.backend.name,
            "encode_first_s": cold_s, "encode_warm_s": warm_s,
            "decode_first_s": dcold_s, "decode_warm_s": dwarm_s}
        say(f"phase 1: {name} [{stripes},{k},{L}] bit-exact vs "
            f"jerasure({ref.core.backend.name}); kernel={kernels} "
            f"encode first {cold_s:.2f}s (compile ~"
            f"{max(0.0, cold_s - warm_s):.2f}s) warm {warm_s:.3f}s; "
            f"decode{list(erasures)} kernel={dkernels} first "
            f"{dcold_s:.2f}s warm {dwarm_s:.3f}s")

    # the served path's own shapes and entry points
    tpu = reg.factory("tpu", dict(RS_K8M4))
    ref = reg.factory("jerasure", dict(RS_K8M4))
    k, m, cs = 8, 4, 4096
    data = rng.integers(0, 256, (max(served_batches), k, cs),
                        dtype=np.uint8)
    want = ref.core.encode_batch(data)
    full = np.concatenate([data, want], axis=1)
    served = {}
    for nb in served_batches:
        h, first_s = _timed(lambda: tpu.encode_batch_async(data[:nb]))
        out, wait_s = _timed(h.wait)
        _check_mesh(h, n_devices, f"encode_batch_async[{nb}]")
        if not np.array_equal(out, want[:nb]):
            raise AssertionError(f"encode_batch_async[{nb}] differs")
        served[f"encode_{nb}"] = first_s + wait_s
    nb = min(served_batches)
    for lost in (0, k):                  # one data, one parity shard
        present = {i: full[:nb, i] for i in range(k + m) if i != lost}
        h, first_s = _timed(
            lambda: tpu.decode_batch_async(present, cs))
        dec, wait_s = _timed(h.wait)
        if not np.array_equal(dec[lost], full[:nb, lost]):
            raise AssertionError(f"decode_batch_async lost={lost} "
                                 f"differs")
        served[f"decode_lost{lost}_{nb}"] = first_s + wait_s
    delta = rng.integers(0, 256, (1, 1, cs), dtype=np.uint8)
    h, first_s = _timed(
        lambda: tpu.delta_encode_batch_async(delta, (3,)))
    dpar, wait_s = _timed(h.wait)
    _check_mesh(h, n_devices, "delta_encode_batch_async")
    if not np.array_equal(dpar, ref.core.delta_parity(delta, (3,))):
        raise AssertionError("delta_encode_batch_async differs")
    served["delta_1"] = first_s + wait_s
    # a square row set (k == m) is the only one whose staged input is
    # donated to the kernel (gf8_fn), under jit and under shard_map
    sq = {"technique": "reed_sol_van", "k": "2", "m": "2", "w": "8"}
    tsq, rsq = reg.factory("tpu", dict(sq)), reg.factory("jerasure",
                                                         dict(sq))
    dsq = rng.integers(0, 256, (64, 2, cs), dtype=np.uint8)
    h = tsq.encode_batch_async(dsq)
    _check_mesh(h, n_devices, "donated encode_batch_async")
    if not np.array_equal(h.wait(), rsq.core.encode_batch(dsq)):
        raise AssertionError("donated encode_batch_async differs")
    report["served_shapes_first_call_s"] = served
    say("phase 1: served-path shapes via the async entry points, "
        "bit-exact; first-call seconds: " +
        ", ".join(f"{n}={s:.2f}" for n, s in served.items()) +
        (f"; outputs sharded over {n_devices} devices"
         if n_devices > 1 else ""))
    report["kernel_calls"] = dict(tpu.core.backend.kernel_calls)
    return report


# ---------------------------------------------------------------------
# phase 2
# ---------------------------------------------------------------------
def _in_flight(names, submit, check, depth: int, timeout: float):
    """Keep ``depth`` aio ops in flight over ``names``; ``check(name,
    completion)`` sees each one after its ack."""
    pending = deque()

    def retire():
        name, comp = pending.popleft()
        rc = comp.wait(timeout)
        if rc != 0:
            raise AssertionError(f"op on {name!r} returned {rc}")
        check(name, comp)
    for name in names:
        if len(pending) >= depth:
            retire()
        pending.append((name, submit(name)))
    while pending:
        retire()


def _read_all(io, model, names, depth, timeout, what):
    def check(name, comp):
        got = comp.reply.out_data[0]
        if bytes(got) != bytes(model[name]):
            raise AssertionError(f"{what}: {name!r} read back "
                                 f"differs from the model")
    _in_flight(names, io.aio_read, check, depth, timeout)


def _wait_for(rad, what: str, cmd: dict, done, timeout: float,
              t_cmd: float) -> dict:
    """Poll a mon command on the smoke's own, already connected client
    until ``done(out)``.  (Cluster.wait_for_* dial a fresh client per
    poll with a fixed 10 s connect budget, which a rebuild sharing this
    interpreter can starve.)"""
    deadline = time.monotonic() + timeout
    while True:
        ret, rs, out = rad.mon_command(cmd, t_cmd)
        if ret != 0:
            raise AssertionError(f"{cmd['prefix']}: {ret} {rs}")
        if done(out):
            return out
        if time.monotonic() > deadline:
            raise TimeoutError(f"{what} not reached in {timeout:.0f}s;"
                               f" last {cmd['prefix']}: {out}")
        time.sleep(0.5)


def _osd_is(osd_id: int, up: bool):
    return lambda out: any(o["osd"] == osd_id and bool(o["up"]) == up
                           for o in out.get("osds", []))


def _sum_counters(dumps, subsystem):
    total = {}
    for d in dumps:
        for key, val in d.get(subsystem, {}).items():
            if isinstance(val, (int, float)):
                total[key] = total.get(key, 0) + val
    return total


def phase_cluster(seed: int, n_osds: int = 13, k: int = 8, m: int = 4,
                  n_objs: int = 128, obj_bytes: int = 4 << 20,
                  n_overwrites: int = 64, n_degraded: int = 16,
                  depth: int = 16, pg_num: int = 32) -> dict:
    import jax

    from ceph_tpu.client.rados import RadosError
    from ceph_tpu.cluster import Cluster, test_config
    from ceph_tpu.osd.osdmap import PGid
    from ceph_tpu.tools.ceph_cli import tell
    from ceph_tpu.utils.machine import machine_factor
    on_tpu = jax.default_backend() == "tpu"
    n_devices = len(jax.devices())
    f = machine_factor()
    t_op = 120 * f
    rng = np.random.default_rng(seed + 1)
    overrides = dict(osd_objectstore="bluestore",
                     ec_tpu_fallback_cpu=False)
    if n_osds > 4:
        # many daemons in one interpreter: slow the heartbeat chatter
        # and the down->out aging so a compile or a GIL stall is not
        # taken for a dead OSD (the k8m4 settings of bench.py)
        overrides.update(osd_heartbeat_interval=2.0,
                         osd_heartbeat_grace=max(20.0, 12.0 * f),
                         mon_osd_down_out_interval=120.0)
    conf = test_config(**overrides)
    times = {}
    with Cluster(n_osds=n_osds, conf=conf) as c:
        for i in range(n_osds):
            c.wait_for_osd_up(i, 30)
        c.create_ec_profile("smoke", plugin="tpu",
                            technique="reed_sol_van", k=str(k),
                            m=str(m))
        c.create_pool("smokep", "erasure", pg_num=pg_num,
                      erasure_code_profile="smoke")
        ret, rs, _ = c.mon_command({
            "prefix": "osd pool set", "pool": "smokep",
            "var": "allow_ec_overwrites", "val": "true"})
        if ret != 0:
            raise AssertionError(f"allow_ec_overwrites: {rs}")
        rad = c.rados(timeout=60 * f)
        io = rad.open_ioctx("smokep")

        # 1. write, every ack received
        model = {f"obj{i}": bytearray(
            rng.integers(0, 256, obj_bytes, dtype=np.uint8).tobytes())
            for i in range(n_objs)}
        names = list(model)
        t0 = time.perf_counter()
        _in_flight(names,
                   lambda n: io.aio_write_full(n, bytes(model[n])),
                   lambda n, comp: None, depth, t_op)
        times["write_s"] = time.perf_counter() - t0
        say(f"phase 2: wrote {n_objs} x {obj_bytes >> 10} KiB "
            f"({n_objs * obj_bytes >> 20} MiB), {depth} in flight, "
            f"every ack received, {times['write_s']:.1f}s")

        # 2. read back
        t0 = time.perf_counter()
        _read_all(io, model, names, depth, t_op, "read-back")
        times["read_s"] = time.perf_counter() - t0
        say(f"phase 2: read back bit-exact, {times['read_s']:.1f}s")

        # 3. sub-stripe overwrites against a plain bytearray model
        deadline = time.monotonic() + 30 * f
        while True:                  # the pool flag reaches the OSDs
            patch = rng.integers(0, 256, 4096, dtype=np.uint8).tobytes()
            try:
                io.write(names[0], patch, 0)
                model[names[0]][:4096] = patch
                break
            except RadosError as e:
                if e.errno != 95 or time.monotonic() > deadline:
                    raise
                time.sleep(0.2)
        sizes = (4, 4, 8, 8, 16, 32, 64)
        plan = []
        for _ in range(n_overwrites):
            size = min(sizes[rng.integers(len(sizes))] << 10,
                       obj_bytes // 2)
            name = names[rng.integers(n_objs)]
            off = int(rng.integers(0, (obj_bytes - size) // 4096 + 1)
                      ) * 4096
            plan.append((name, off, rng.integers(
                0, 256, size, dtype=np.uint8).tobytes()))

        def overwrite(i):
            name, off, patch = plan[i]
            # ops on one object apply in submission order
            model[name][off:off + len(patch)] = patch
            return io.aio_write(name, patch, off)
        t0 = time.perf_counter()
        _in_flight(range(n_overwrites), overwrite,
                   lambda i, comp: None, depth, t_op)
        times["overwrite_s"] = time.perf_counter() - t0
        touched = sorted({p[0] for p in plan} | {names[0]})
        _read_all(io, model, touched, depth, t_op, "after overwrite")
        say(f"phase 2: {n_overwrites} overwrites of 4-64 KiB acked "
            f"and read back bit-exact, {times['overwrite_s']:.1f}s")

        # 4. lose an OSD with its data: degraded reads, then rebuild
        clean = {"prefix": "health"}, lambda out: out.get("all_clean")
        _wait_for(rad, "clean before the kill", *clean, 120 * f, t_op)
        victim = n_osds - 1
        c.kill_osd(victim, lose_data=True)
        _wait_for(rad, f"osd.{victim} down", {"prefix": "osd dump"},
                  _osd_is(victim, False), 120 * f, t_op)
        t0 = time.perf_counter()
        _read_all(io, model, names[:n_degraded], depth, t_op,
                  "degraded read")
        times["degraded_read_s"] = time.perf_counter() - t0
        say(f"phase 2: osd.{victim} killed with data loss; "
            f"{min(n_degraded, n_objs)} degraded reads bit-exact, "
            f"{times['degraded_read_s']:.1f}s")
        c.revive_osd(victim)
        _wait_for(rad, f"osd.{victim} up", {"prefix": "osd dump"},
                  _osd_is(victim, True), 120 * f, t_op)
        t0 = time.perf_counter()
        _wait_for(rad, "clean after the rebuild", *clean,
                  max(600.0, 240.0 * f), t_op)
        times["rebuild_s"] = time.perf_counter() - t0
        _read_all(io, model, names, depth, t_op, "after rebuild")
        say(f"phase 2: osd.{victim} revived empty, clean after "
            f"{times['rebuild_s']:.1f}s, everything read back "
            f"bit-exact")

        # 5. one deep scrub per PG, zero inconsistencies
        t_scrub = time.time()
        pgids = [str(PGid(io.pool_id, seed_))
                 for seed_ in range(pg_num)]
        for pgid in pgids:
            ret, rs, _ = rad.mon_command({"prefix": "pg deep-scrub",
                                          "pgid": pgid}, t_op)
            if ret != 0:
                raise AssertionError(f"pg deep-scrub {pgid}: {rs}")
        stats = _wait_for(
            rad, "a deep scrub of every PG", {"prefix": "pg dump"},
            lambda out: all(out["pg_stats"].get(p, {}).get(
                "last_deep_scrub", 0) >= t_scrub for p in pgids),
            max(600.0, 240.0 * f), t_op)["pg_stats"]
        bad = {p: stats[p] for p in pgids
               if stats[p].get("num_scrub_errors")
               or stats[p].get("inconsistent")}
        if bad:
            raise AssertionError(f"deep scrub found inconsistencies: "
                                 f"{bad}")
        times["scrub_s"] = time.time() - t_scrub
        say(f"phase 2: {pg_num} PGs deep-scrubbed, zero "
            f"inconsistencies, {times['scrub_s']:.1f}s")

        # the counters, over the admin-command path
        perf, device, store = [], [], []
        for i in range(n_osds):
            for prefix, sink in (("perf dump", perf),
                                 ("dump_device", device),
                                 ("dump_store", store)):
                ret, rs, out = tell(rad, f"osd.{i}",
                                    {"prefix": prefix}, 30 * f)
                if ret != 0:
                    raise AssertionError(
                        f"tell osd.{i} {prefix}: {ret} {rs}")
                sink.append(out)
        scrub = {"windows": 0, "device_windows": 0, "device_errors": 0}
        for osd in c.osds.values():
            for pg in osd.pgs.values():
                be = pg.backend
                scrub["windows"] += getattr(be, "scrub_windows", 0)
                scrub["device_windows"] += getattr(
                    be, "scrub_device_windows", 0)
                scrub["device_errors"] += getattr(
                    be, "scrub_device_errors", 0)

    bat = _sum_counters(perf, "ec_batcher")
    dev = _sum_counters(perf, "ec_device")
    lanes = {lane: {key: sum(d["lanes"][lane][key] for d in device)
                    for key in ("reqs", "twin_reqs")}
             for lane in ("encode", "decode", "delta")}
    kernels = {}
    for d in device:             # one shared backend: same everywhere
        kernels.update(d["kernels"])
    csum = {key: sum(s["csum"][key] for s in store)
            for key in ("batches", "blocks")}
    mesh_devices = max(p.get("ec_device", {}).get("mesh_devices", 0)
                       for p in perf)
    twin_verdicts = {key: val for key, val in dev.items()
                     if val and key.split("route_")[-1] in
                     ("pin", "learned", "breaker_open")}
    say(f"phase 2: lanes (requests/on the twin): " + ", ".join(
        f"{lane} {v['reqs']}/{v['twin_reqs']}"
        for lane, v in lanes.items()) +
        f"; ec_batcher device_reqs={bat.get('device_reqs')} "
        f"cpu_reqs={bat.get('cpu_reqs')} "
        f"device_errors={bat.get('device_errors')} "
        f"breaker_open={bat.get('breaker_open')} "
        f"ec_encode_errors={bat.get('ec_encode_errors')}; "
        f"kernels={kernels}; bluestore csum {csum}; scrub {scrub}; "
        f"mesh_devices={mesh_devices}")
    say(f"phase 2: router as prewarm and the learner left it (not "
        f"asserted): {json.dumps(device[0]['router'])}")
    errors = [e for d in device for e in d["prewarm_errors"]] + \
        [d["last_device_error"] for d in device
         if d["last_device_error"]]
    checks = {
        "ec_batcher.device_reqs > 0": bat.get("device_reqs", 0) > 0,
        "ec_batcher.cpu_reqs == 0": bat.get("cpu_reqs", 0) == 0,
        "ec_batcher.device_errors == 0":
            bat.get("device_errors", 0) == 0,
        "ec_batcher.breaker_open == 0":
            bat.get("breaker_open", 0) == 0,
        "ec_batcher.ec_encode_errors == 0":
            bat.get("ec_encode_errors", 0) == 0,
        "no routing verdict sent a group to the twin":
            not twin_verdicts,
        "no prewarm or device error recorded": not errors,
        "deep-scrub device_errors == 0": scrub["device_errors"] == 0,
    }
    for lane, v in lanes.items():
        checks[f"{lane}: device requests > 0"] = \
            v["reqs"] - v["twin_reqs"] > 0
        checks[f"{lane}: twin requests == 0"] = v["twin_reqs"] == 0
    if on_tpu:
        # these routes switch to the device only off-CPU
        checks["deep-scrub device windows > 0"] = \
            scrub["device_windows"] > 0
        checks["served w=8 dispatches rode gf_mxu_pallas only"] = \
            "gf8_xor_chain" not in kernels and \
            kernels.get("gf_mxu_pallas", 0) > 0
    if n_devices > 1:
        checks[f"ec_device.mesh_devices == {n_devices}"] = \
            mesh_devices == n_devices
    failed = [name for name, ok in checks.items() if not ok]
    if failed:
        raise AssertionError(
            f"phase 2 counter checks failed: {failed}; twin verdicts "
            f"{twin_verdicts}; errors {errors}")
    say(f"phase 2: {len(checks)} counter checks hold")
    return {"times": times, "lanes": lanes, "ec_batcher": bat,
            "ec_device": dev, "kernels": kernels, "csum": csum,
            "scrub": scrub, "mesh_devices": mesh_devices,
            "router": device[0]["router"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds every byte the smoke writes")
    args = ap.parse_args(argv)
    t0 = time.perf_counter()
    device = phase_device()
    report = {"device": device, "seed": args.seed}
    report["codec"] = phase_codec(args.seed)
    t1 = time.perf_counter()
    report["cluster"] = phase_cluster(args.seed)
    report["wall_s"] = {"codec": t1 - t0,
                        "cluster": time.perf_counter() - t1}
    say(f"chip_smoke: all phases passed; codec {t1 - t0:.1f}s, "
        f"cluster {report['wall_s']['cluster']:.1f}s")
    out_dir = os.path.join(HERE, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "chip_smoke.json"), "w") as fh:
        json.dump(report, fh, indent=1, default=str)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
