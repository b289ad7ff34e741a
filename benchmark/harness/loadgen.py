"""The one load generator: a closed loop at a fixed depth.

A corrected copy of ``ceph_tpu/tools/rados_cli.py::bench`` (the
ObjBencher loop).  What is different, and why:

* Each of the ``depth`` slots is a caller of its own: it submits one
  op, blocks on that op's completion (``Completion.wait``, an Event)
  and stamps the ack on waking.  No loop scans the in-flight set, so
  the generator costs the interpreter nothing while it waits, and an
  ack is stamped at the ack, not at the next scan.
* The load is continuous: callers start before the window and run on
  past its end; the window is cut out of their stamps afterwards by
  ack time.  Neither the ramp from an empty pipeline nor the drain is
  inside a window.
* Nothing is drawn or built while the load runs: names of populated
  objects, offsets, payload indices and the order of op classes are
  laid out per caller, from the seed, before the first op.

Traffic files give: ``depth``, ``object_bytes``, ``names``,
``populate_objects`` and ``ops``, a weighted list of op classes
``{"op": write_full|write|read, "io_bytes", "target": new|populated,
"order": seq|uniform, "weight"}``.
"""
import contextlib
import itertools
import threading
import time

import numpy as np

PLAN_OPS = 1 << 15          # ops laid out per caller; the plan wraps
ETIMEDOUT = -110


def op_ok(rec, ops) -> bool:
    """A record (t_submit, t_ack, class, object, offset, payload, rc,
    bytes, kept reply) of an op that was answered, and in full."""
    return rec[6] == 0 and rec[7] == ops[rec[2]]["io_bytes"]


class LoadGen:
    def __init__(self, io, model, traffic: dict, annotate: bool = False,
                 op_timeout: float = 120.0):
        self.io = io
        self.model = model
        self.depth = int(traffic["depth"])
        self.ops = traffic["ops"]
        self.object_bytes = int(traffic["object_bytes"])
        self.n_populated = int(traffic.get("populate_objects", 0))
        self.keep_every = int(traffic.get("check", {}).get("keep_every", 0))
        self.op_timeout = op_timeout
        self.span = contextlib.nullcontext
        if annotate:
            import jax
            self.span = jax.profiler.TraceAnnotation
        self.pop_names = [model.name(n) for n in range(self.n_populated)]
        self._new = itertools.count(self.n_populated)
        self._seq = itertools.count()
        rng = model.rng
        self.seq_start = int(rng.integers(0, max(self.n_populated, 1)))
        w = np.array([o.get("weight", 1) for o in self.ops], dtype=float)
        self.plans = []
        for j in range(self.depth):
            cls = rng.choice(len(self.ops), size=PLAN_OPS, p=w / w.sum())
            obj = rng.integers(0, max(self.n_populated, 1), PLAN_OPS)
            pay = rng.integers(0, max(len(model.patches), 1), PLAN_OPS)
            blk = np.zeros(PLAN_OPS, dtype=np.int64)
            for c, o in enumerate(self.ops):
                if o["op"] == "read" and o["io_bytes"] < self.object_bytes:
                    n_blk = self.object_bytes // o["io_bytes"]
                    blk[cls == c] = rng.integers(0, n_blk, PLAN_OPS)[cls == c]
                if o["op"] != "write":
                    continue
                # caller j owns the blocks whose number is j mod depth:
                # no two writes in flight ever overlap
                n_blk = self.object_bytes // o["io_bytes"]
                if n_blk < self.depth:
                    raise ValueError("fewer blocks per object than callers")
                own = rng.integers(0, n_blk // self.depth, PLAN_OPS)
                blk[cls == c] = (own * self.depth + j)[cls == c]
            self.plans.append((cls.tolist(), obj.tolist(), blk.tolist(),
                               pay.tolist()))
        self.records = [[] for _ in range(self.depth)]
        self.errors = []
        self.t0 = float("inf")          # set when the window opens
        self._stop = False
        self._threads = []

    # -- the load ---------------------------------------------------------
    def start(self) -> None:
        for j in range(self.depth):
            t = threading.Thread(target=self._caller, args=(j,),
                                 name=f"bench-caller-{j}", daemon=True)
            self._threads.append(t)
        for t in self._threads:
            t.start()

    def stop_and_drain(self, timeout: float = 180.0) -> None:
        self._stop = True
        deadline = time.monotonic() + timeout
        for t in self._threads:
            t.join(max(0.0, deadline - time.monotonic()))
        alive = [t.name for t in self._threads if t.is_alive()]
        if alive:
            self.errors.append(f"callers never drained: {alive}")

    def _caller(self, j: int) -> None:
        io, model, ops, span = self.io, self.model, self.ops, self.span
        cls_seq, obj_seq, blk_seq, pay_seq = self.plans[j]
        rec = self.records[j]
        now = time.monotonic
        keep_every = self.keep_every
        keep_phase = j % keep_every if keep_every else 0
        n_pop, pop_names = self.n_populated, self.pop_names
        i = 0
        try:
            while not self._stop:
                p = i % PLAN_OPS
                c = cls_seq[p]
                o = ops[c]
                kind, nbytes = o["op"], o["io_bytes"]
                off = pay = 0
                with span("client.submit"):
                    if kind == "write_full":
                        n = next(self._new)
                        t_s = now()
                        comp = io.aio_write_full(model.name(n),
                                                 model.base(n))
                    elif kind == "read":
                        if o.get("order", "seq") == "seq":
                            n = (self.seq_start + next(self._seq)) % n_pop
                        else:
                            n = obj_seq[p]
                        off = blk_seq[p] * nbytes
                        t_s = now()
                        comp = io.aio_read(pop_names[n], nbytes, off)
                    elif kind == "write":
                        n, pay = obj_seq[p], pay_seq[p]
                        off = blk_seq[p] * nbytes
                        t_s = now()
                        comp = io.aio_write(pop_names[n],
                                            model.patches[pay], off)
                    else:
                        raise ValueError(f"unknown op {kind!r}")
                with span("client.wait"):
                    try:
                        rc = comp.wait(self.op_timeout)
                    except TimeoutError:
                        rc = ETIMEDOUT
                t_a = now()
                kept = None
                got = nbytes
                if kind == "read" and rc == 0:
                    data = comp.reply.out_data[0]
                    got = len(data)
                    if keep_every and i % keep_every == keep_phase \
                            and t_a >= self.t0:
                        kept = data
                rec.append((t_s, t_a, c, n, off, pay, rc, got, kept))
                i += 1
        except Exception as e:          # a caller that dies is a failed run
            self.errors.append(f"caller {j}: {type(e).__name__}: {e}")

    # -- what happened ----------------------------------------------------
    def all_records(self):
        return [r for rec in self.records for r in rec]

    def in_window(self, t0: float, seconds: float):
        t1 = t0 + seconds
        return [r for r in self.all_records() if t0 <= r[1] < t1]
