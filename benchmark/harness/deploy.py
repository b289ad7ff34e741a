"""The system under test: one cluster as a configuration file states it.

The cluster set-up is a copy of chip_smoke.py phase 2's (its clock is
not): mon and OSDs as threads of this process, a pool on the tpu
plugin, one connected client.  Everything that differs between
deployments comes from ``configs/<name>.json``.
"""
import threading
import time

from . import spec

PREWARM_THREAD = "ec-prewarm"       # osd/batcher.py names it so
LANES = ("encode", "decode", "delta")


def chain_columns(key: tuple, layout: dict) -> int:
    """The chunks one call of a compiled chain takes in, read from its
    cache key as ``chain_keys.json`` says for the key's prefix."""
    def at(path):
        v = key
        for i in path:
            v = v[i]
        return v
    n = at(layout["columns"])
    n = n if isinstance(n, int) else len(n)
    return n // at(layout["per"]) if "per" in layout else n


class Deployment:
    def __init__(self, config: dict):
        from ceph_tpu.cluster import Cluster
        from ceph_tpu.utils.config import Config
        self.config = config
        self.k = int(config["pool"]["profile"]["k"])
        self.m = int(config["pool"]["profile"]["m"])
        self.stripe_unit = int(config["stripe_unit"])
        self.n_osds = int(config["osds"])
        self.dead = set()
        self.t_cmd = 120.0
        self.cluster = Cluster(n_osds=self.n_osds,
                               conf=Config(dict(config["conf"])),
                               n_mons=int(config.get("mons", 1)))
        self.rad = None
        self.io = None

    # -- set-up ---------------------------------------------------------
    def boot(self) -> None:
        self.cluster.start()
        self.rad = self.cluster.rados(timeout=60.0)
        self._poll("every OSD up", {"prefix": "osd dump"},
                   lambda out: sum(1 for o in out.get("osds", [])
                                   if o["up"]) >= self.n_osds, 180.0)

    def make_pool(self) -> None:
        pool = self.config["pool"]
        profile = {k: str(v) for k, v in pool["profile"].items()}
        self.cluster.create_ec_profile("benchprofile", **profile)
        self.cluster.create_pool(pool["name"], pool["type"],
                                 pg_num=int(self.config["pg_num"]),
                                 erasure_code_profile="benchprofile")
        if pool.get("allow_ec_overwrites"):
            ret, rs, _ = self.rad.mon_command({
                "prefix": "osd pool set", "pool": pool["name"],
                "var": "allow_ec_overwrites", "val": "true"}, self.t_cmd)
            if ret != 0:
                raise RuntimeError(f"allow_ec_overwrites: {ret} {rs}")
        self.io = self.rad.open_ioctx(pool["name"])
        self.wait_maps()
        self.check_stripe_width()
        self._poll("every PG active+clean", {"prefix": "health"},
                   lambda out: out.get("all_clean"), 180.0)

    def check_stripe_width(self) -> None:
        """The deployment is what the file states: the pool the program
        made has the file's stripe unit, by the map every daemon holds
        (the mon derives it from the profile and the code's alignment,
        not from this file)."""
        made = self.rad.objecter.osdmap.get_pool(
            self.config["pool"]["name"]).stripe_width
        stated = self.k * self.stripe_unit
        if made != stated:
            raise SystemExit(
                f"benchmark: the pool's stripe_width is {made} bytes, "
                f"and {self.config['name']} states k * stripe_unit = "
                f"{self.k} * {self.stripe_unit} = {stated}: the "
                f"deployment is not the one the file describes")

    def wait_maps(self, timeout: float = 120.0) -> None:
        """Every live daemon and the client hold the mon's newest map
        (a pool flag or a down mark has reached them all)."""
        deadline = time.monotonic() + timeout
        while True:
            want = self.cluster.mon.osdmap.epoch
            have = [o.osdmap.epoch for o in self.cluster.osds.values()
                    if o is not None]
            have.append(self.rad.objecter.osdmap.epoch)
            if min(have) >= want:
                return
            if time.monotonic() > deadline:
                raise TimeoutError(f"maps at {have}, mon at {want}")
            time.sleep(0.1)

    def wait_prewarm(self, timeout: float = 600.0) -> None:
        """The batcher compiles a pool geometry's shapes on a
        background thread; the window may not open while it runs."""
        deadline = time.monotonic() + timeout
        for t in threading.enumerate():
            if t.name == PREWARM_THREAD:
                t.join(max(0.0, deadline - time.monotonic()))
                if t.is_alive():
                    raise TimeoutError("ec-prewarm still compiling")

    def warm_cached_programs(self, traffic: dict) -> int:
        """Run every GF program the warm-up load has built (the chains
        whose key prefix ``chain_keys.json`` lists, byte-domain and
        packet layout alike) at every batch size the batcher can form from
        this traffic, so that no (erasure signature, batch bucket) pair
        meets its first call inside the window; -> calls made.

        The batcher pads a group of n coalesced requests to the next
        power of two of its stripes, and jit compiles per padded shape;
        a pair of requests with one signature in one 200 us window is
        rare enough to first happen minutes in.  This reads the shared
        backend's cache of compiled chains (``_chain_lru``), which is
        the program's inside: if that moves, nothing is warmed here and
        ``dispatch.compiles_in_window`` says so."""
        import jax.numpy as jnp
        from ceph_tpu.ec.plugins.tpu import shared_backend
        from ceph_tpu.ops.jax_engine import _bucket_batch
        layouts = spec.chain_keys()
        lru = getattr(shared_backend(), "_chain_lru", None)
        with lru._lock:
            chains = [(key, fn) for key, fn in lru._d.items()
                      if key and key[0] in layouts]
        width = self.k * self.stripe_unit
        buckets = set()
        for op in traffic["ops"]:
            stripes = max(1, -(-op["io_bytes"] // width))
            most = max(1, min(int(traffic["depth"]), 1024 // stripes))
            buckets |= {_bucket_batch(n * stripes)
                        for n in range(1, most + 1)}
        calls = 0
        for key, fn in chains:
            cols = chain_columns(key, layouts[key[0]])
            for nb in sorted(buckets):
                fn(jnp.zeros((nb, cols, self.stripe_unit),
                             dtype=jnp.uint8)).block_until_ready()
                calls += 1
        return calls

    def kill_osd_with_data(self, which) -> int:
        victim = self.n_osds - 1 if which == "last" else int(which)
        self.cluster.kill_osd(victim, lose_data=True)
        self.dead.add(victim)
        # the operator's `ceph osd down`: the heartbeat grace (30 s)
        # would otherwise be 30 s of every run's set-up
        ret, rs, _ = self.rad.mon_command(
            {"prefix": "osd down", "ids": [victim]}, self.t_cmd)
        if ret != 0:
            raise RuntimeError(f"osd down {victim}: {ret} {rs}")
        self._poll(f"osd.{victim} marked down", {"prefix": "osd dump"},
                   lambda out: any(o["osd"] == victim and not o["up"]
                                   for o in out.get("osds", [])), 180.0)
        self.wait_maps()
        return victim

    def _poll(self, what, cmd, done, timeout) -> dict:
        deadline = time.monotonic() + timeout
        while True:
            ret, rs, out = self.rad.mon_command(cmd, self.t_cmd)
            if ret != 0:
                raise RuntimeError(f"{cmd['prefix']}: {ret} {rs}")
            if done(out):
                return out
            if time.monotonic() > deadline:
                raise TimeoutError(f"{what}: not reached in {timeout}s; "
                                   f"last {cmd['prefix']}: {out}")
            time.sleep(0.25)

    def stop(self) -> None:
        self.cluster.stop()

    # -- counters: read before and after a window, never inside it -------
    def live_osds(self):
        return [o for i, o in sorted(self.cluster.osds.items())
                if o is not None]

    def lane_counts(self) -> dict:
        """What ``dump_device`` reports under ``lanes``, ``kernels``
        and ``device_errors``, summed over the OSDs, read from the
        fields that command reads (a few attribute loads: cheap enough
        to take at the window's two edges)."""
        lanes = {lane: {"reqs": 0, "twin_reqs": 0} for lane in LANES}
        calls = dict.fromkeys(LANES, 0)
        errors = 0
        kernels = {}
        for osd in self.live_osds():
            b = osd.encode_batcher
            for lane, reqs, twin, n_calls in (
                    ("encode", b.reqs_total, b.cpu_reqs, b.calls),
                    ("decode", b.dec_reqs, b.dec_cpu_reqs, b.dec_calls),
                    ("delta", b.delta_reqs, b.delta_cpu_reqs,
                     b.delta_calls)):
                lanes[lane]["reqs"] += reqs
                lanes[lane]["twin_reqs"] += twin
                calls[lane] += n_calls
            errors += b.device_errors
            backend = b._last_backend
            if backend is not None:      # one backend, shared by all
                kernels = dict(getattr(backend, "kernel_calls", {}))
        return {"lanes": lanes, "calls": calls, "kernels": kernels,
                "device_errors": errors,
                "osdmap_epoch": self.cluster.mon.osdmap.epoch}

    def snapshot(self) -> dict:
        """The heavier surfaces, over the admin-command table: the
        client's hop ledgers and every primary's critical path."""
        crit = {"ops": 0, "op_seconds_total": 0.0, "stage_seconds": {}}
        for osd in self.live_osds():
            ret, rs, out = osd._exec_command(
                {"prefix": "dump_critical_path"})
            crit["ops"] += out.get("ops", 0)
            crit["op_seconds_total"] += out.get("op_seconds_total", 0.0)
            for s, v in out.get("stage_seconds", {}).items():
                crit["stage_seconds"][s] = \
                    crit["stage_seconds"].get(s, 0.0) + v
        obj = self.rad.objecter
        return {"hops_write": obj.hops.dump(),
                "hops_read": obj.hops_read.dump(),
                "critical_path": crit}

    # -- what the stores hold -------------------------------------------
    def shard_index(self, names) -> dict:
        """(object name, shard) -> [(osd id, store, collection, ghobject)]
        over the stores of the OSDs that are alive."""
        want = set(names)
        index = {}
        for osd_id, store in sorted(self.cluster.stores.items()):
            if osd_id in self.dead:
                continue
            for coll in store.list_collections():
                for obj in store.collection_list(coll):
                    if obj.oid in want and obj.shard >= 0:
                        index.setdefault((obj.oid, obj.shard), []).append(
                            (osd_id, store, coll, obj))
        return index


def diff(after, before):
    """after - before over nested dicts of numbers."""
    if isinstance(after, dict):
        before = before or {}
        return {k: diff(v, before.get(k)) for k, v in after.items()}
    if isinstance(after, bool) or not isinstance(after, (int, float)):
        return after
    return after - (before or 0)
