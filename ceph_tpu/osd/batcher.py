"""Cross-op TPU stripe batcher — the OSD-level encode coalescer.

This is the framework's "batching point" (SURVEY.md §3.1): where the
reference encodes each write's stripes on the submitting thread inside
ECBackend::try_reads_to_commit (reference src/osd/ECBackend.cc:1939,
via ECUtil::encode's per-stripe loop, src/osd/ECUtil.cc:136-148), a
TPU pays per *device call*, not per stripe — so the win is gathering
stripes from MANY in-flight ops (across PGs, one batcher per OSD) into
ONE batched MXU call.

Mechanics:

* ``submit()`` (called under the PG lock from the EC write pipeline)
  enqueues an encode request keyed by codec geometry and wakes the
  collector.  The submitting thread never blocks on the device.
* The collector thread waits ``ec_tpu_queue_window_us`` from the first
  queued request (or until ``ec_tpu_batch_stripes`` stripes are
  pending) for more ops to arrive, then concatenates each geometry
  group to one ``[N, k, chunk]`` array and issues a single
  ``encode_batch_async`` device call — h2d staging, MXU compute and
  parity d2h overlap across consecutive batches exactly like the
  bench's double buffering.
* Parity is split back per request and each continuation runs in
  submission order (per-PG FIFO holds: the PG pipeline admits one
  encode per PG at a time, and one collector drains batches serially).

Reconstruction (``submit_decode``) and parity-delta (``submit_delta``)
requests ride the same collector: encode, decode and delta are three
rows of ONE lane table (``_Lane``), and the routing ladder, its
learner, the dispatch, the join and the twin completion are written
once over it.

Locking: ``submit`` takes only the batcher lock; continuations take
the owning PG's lock while the batcher lock is dropped — no ordering
cycle with the op workers (which take PG lock then ``submit``).

Reference anchors: the op queue this rides behind is the sharded work
queue (reference src/osd/OSD.cc:9612 enqueue_op -> op_shardedwq); the
in-order commit contract it must preserve is ECBackend::check_ops
(reference src/osd/ECBackend.cc:2151-2156).
"""
from __future__ import annotations

import queue
import threading
import time
import traceback
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from . import ecutil
from ..ops.engine import rows_cache_stats
from ..utils import copytrack
from ..utils import faults as faultlib
from ..utils.device_ledger import DeviceLedgerAccum, overlap_stats
from ..utils.log import derr_once
from ..utils.tracer import section


class _Req:
    """One queued encode.  ``data`` may be bytes, bytearray,
    memoryview or a uint8 ndarray — the caller hands over ownership
    and must not mutate the buffer until ``cb`` fires."""
    lane = "enc"

    def __init__(self, ec_impl, sinfo: ecutil.StripeInfo, data,
                 cb: Callable[[Dict[int, bytes]], None], tracked=None):
        self.ec_impl = ec_impl
        self.sinfo = sinfo
        self.data = data
        self.cb = cb
        self.nbytes = ecutil.nbytes_of(data)
        self.nstripes = self.nbytes // sinfo.stripe_width
        self.tracked = tracked       # OpTracker handle (stage events)
        self.t_enq = time.monotonic()
        self.done = False            # cb delivered (guards double-fail)

    def as_array(self, k: int) -> np.ndarray:
        """[nstripes, k, chunk] view of the request buffer — no copy."""
        return ecutil.as_stripe_array(self.data, self.nstripes, k,
                                      self.sinfo.chunk_size)


class _DecReq:
    """One queued reconstruction: rebuild ``want - have`` shard chunks
    from the equal-length chunk buffers in ``have``."""
    lane = "dec"

    def __init__(self, ec_impl, sinfo: ecutil.StripeInfo,
                 have: Dict[int, bytes], want,
                 cb: Callable[[Optional[Dict[int, bytes]]], None]):
        self.ec_impl = ec_impl
        self.sinfo = sinfo
        self.have = have
        self.want = frozenset(want)
        self.cb = cb
        self.done = False
        total = ecutil.nbytes_of(next(iter(have.values())))
        self.nstripes = total // sinfo.chunk_size
        self.t_enq = time.monotonic()


class _DeltaReq:
    """One queued parity-delta encode (sub-stripe overwrite RMW):
    ``delta`` holds old XOR new chunk bytes for the DIRTY data
    columns only, laid out ``[nstripes, D, chunk]`` for
    D = len(dirty_cols).  GF(2^8) linearity makes the parity update
    ``new_parity = old_parity XOR M[:,dirty]·Δdata``, so only the
    dirty columns ride the device — the rider's ``cb`` receives
    {parity_shard_index: Δparity chunk bytes} to XOR into the
    stored parity chunks (store-level ``xor_write``)."""
    lane = "delta"

    def __init__(self, ec_impl, sinfo: ecutil.StripeInfo, delta,
                 dirty_cols,
                 cb: Callable[[Optional[Dict[int, bytes]]], None],
                 tracked=None):
        self.ec_impl = ec_impl
        self.sinfo = sinfo
        self.delta = delta
        self.dirty_cols = tuple(dirty_cols)
        self.cb = cb
        self.tracked = tracked
        self.nbytes = ecutil.nbytes_of(delta)
        self.nstripes = self.nbytes // (
            len(self.dirty_cols) * sinfo.chunk_size)
        self.t_enq = time.monotonic()
        self.done = False

    def as_array(self, ncols: int) -> np.ndarray:
        """[nstripes, D, chunk] view of the delta buffer — no copy."""
        return ecutil.as_stripe_array(self.delta, self.nstripes,
                                      ncols, self.sinfo.chunk_size)


class _BatchTwin:
    """Device-free execution twin with the BATCHED codec API: encode
    and decode run as ONE kernel call over a whole [N, k, chunk]
    stripe batch — through the native C++ GF kernels when the
    toolchain is available, numpy otherwise.  This is what a coalesced
    group executes on when the learned crossover routes it off the
    device: the coalescing win (one call for many ops' stripes) is
    preserved even when the device round trip would lose, where the
    reference encodes stripe-by-stripe on the submitting thread
    (reference src/osd/ECUtil.cc:136-148 per-stripe loop).

    Wraps a jerasure-plugin codec of the same geometry (bit-exact by
    the corpus contract) and exposes ``encode_batch`` /
    ``decode_batch`` like the tpu plugin, so ``ecutil.encode/decode``
    take their batched paths."""

    def __init__(self, base):
        self.base = base
        try:
            from ..ops import native as native_mod
            base.core.backend = native_mod.NativeBackend()
        except Exception:
            pass                     # no toolchain: numpy stays

    def __getattr__(self, name):
        return getattr(self.base, name)

    def encode_batch(self, data: np.ndarray) -> np.ndarray:
        return self.base.core.encode_batch(
            np.asarray(data, dtype=np.uint8))

    def decode_batch(self, present, chunk_len: int):
        arrays = {i: np.asarray(c, dtype=np.uint8)
                  for i, c in present.items()}
        return self.base.core.decode_chunks(arrays, chunk_len)


def _geometry_key(ec_impl, sinfo: ecutil.StripeInfo) -> Tuple:
    """Requests may share one device call iff they encode with the
    same coding matrix over the same chunk size.  The matrix is a
    deterministic function of (plugin, technique, k, m, w,
    packetsize), so that tuple + chunk_size is a sound key even
    across codec instances from different PGs of the same pool."""
    return (type(ec_impl).__name__,
            ec_impl.get_data_chunk_count(),
            ec_impl.get_coding_chunk_count(),
            getattr(ec_impl, "technique", ""),
            getattr(ec_impl, "w", 0),
            getattr(ec_impl, "packetsize", 0),
            sinfo.chunk_size)


def _cat(parts):
    """The tiles of one group as one stack: arrays along the stripe
    axis, a decode's {shard: [B, cs]} map shard by shard."""
    if len(parts) == 1:
        return parts[0]
    if isinstance(parts[0], dict):
        return {s: np.concatenate([p[s] for p in parts], axis=0)
                for s in parts[0]}
    return np.concatenate(parts, axis=0)


def _nbytes(stack) -> int:
    if isinstance(stack, dict):
        return sum(v.nbytes for v in stack.values())
    return stack.nbytes


def _column_views(cols) -> Tuple[Dict[int, memoryview], int]:
    """{shard: [n, chunk] column of a stack} -> ({shard: 1-D byte
    memoryview}, bytes copied).

    The column gathers are the ONE unavoidable copy on the output
    side — a [nstripes, shards, chunk] stack interleaves shards, so
    each shard's chunks must be made contiguous exactly once.  The
    views then ride by reference through the sub-write transactions,
    the wire iovecs and the store with no further bytes()/tobytes()
    round trips.  memoryview compares by content, so callers that
    check chunks against reference encodes with == still work."""
    out: Dict[int, memoryview] = {}
    copied = 0
    for shard, src in cols.items():
        col = np.ascontiguousarray(src)
        if col is not src:
            copied += col.nbytes
        out[shard] = memoryview(col).cast("B")
    return out, copied


class _Lane:
    """What one of the batcher's three lanes (encode, decode,
    parity-delta) is, as far as the lanes differ: names, where the
    learned crossover lives, how a group's bytes are reckoned, and the
    hooks that stack a group's requests, launch one tile on the
    plug-in, split a result back to the riders and run the group off
    the device.  The ladder, the learner, the dispatch, the join and
    the twin completion are written once in EncodeBatcher, over the
    three instances _ENC, _DEC and _DELTA.

    Each lane defines ``width(key, reqs)`` (columns of one stripe of a
    request's array, where ``form`` is this one),
    ``launch(key, reqs, stack, lo, hi)`` (one tile of the stack on the
    plug-in's async entry), ``twin_call(impl, key, reqs, stack)`` (the
    whole stack in ONE batched call off the async path),
    ``single(b, key, r)`` (the per-request fallback behind it) and
    ``split(b, key, reqs, result)`` (yield each rider's output, in
    request order)."""
    name = ""            # queue-key tag, request.lane, section lane=
    prefix = ""          # <prefix>route_<reason> counters, <prefix>route
                         # recorder events, ec_<prefix>batch_* OSD counters
    group = ""           # the ledgers' "group" tag; the device-failure
                         # kind of the lane's synchronous device call
    # instance counters: calls, requests, requests on the twin,
    # requests that shared a call
    counters: Tuple[str, str, str, str] = ("", "", "", "")
    # class attribute that holds the learned crossover.  Decode and
    # delta keep their own (recovery moves k survivor chunks IN per
    # erased chunk OUT, a delta D dirty columns IN per m parity
    # columns OUT: neither has encode's k-in/m-out economics), but
    # while it reads 0 they judge by encode's — the device and the
    # link are the same hardware, so encode's measurement beats
    # flying blind on the first rebuild window (_min_bytes)
    crossover = "_min_device_bytes"
    concat_site = ""     # copytrack site of the multi-request concat
    # the plug-in's async entry and the method that says whether this
    # geometry can ride it (None: submit() queues no other codec)
    async_entry = ""
    async_probe: Optional[str] = None
    # handle of a group whose plug-in lacks the async entry
    without_async = "twin"
    # a failed device dispatch or join falls the WHOLE group to the
    # batched twin (zero client errors); encode alone falls to
    # per-request _cpu_encode, uncounted
    fails_to_twin = True
    # the plug-in's own synchronous batched call stands in for a twin
    # that cannot be built, and serves without_async="sync"
    sync_call = False
    # tracked.mark_event names, after a device dispatch / at a twin
    # group's start (decode requests carry no tracker)
    dispatch_event: Optional[str] = None
    twin_event: Optional[str] = None
    # -- what each lane's twin completion books today.  The lanes
    # disagree in ways that look like oversights (ROADMAP.md queue 3
    # lists them); they are kept as they were, stated here once.
    twin_books = True    # queue wait, concat copy, stage seconds,
                         # ec_batcher counters, cpu_calls, ledger size
    twin_is_call = True  # a twin group counts in <calls>
    twin_perf: Tuple[str, ...] = ()     # ec_<prefix>batch_<x> bumped
    # instance counters one rider of the per-request fallback bumps
    single_counts: Tuple[str, ...] = ()

    def geometry(self, key: Tuple) -> Tuple:
        return key[1]

    def bucket(self, key: Tuple) -> Tuple:
        """Key of the lane's _cpu_bps / _dev_bps entry: one per
        geometry (not per erasure or dirty signature — the GF
        matmul's bytes/s is nearly independent of it: compute and
        input scale together)."""
        return (self.name, self.geometry(key))

    def route_fields(self, key: Tuple) -> dict:
        """Lane-specific fields of the recorder's route event."""
        return {}

    def has_async(self, impl) -> bool:
        if self.async_probe is None:
            return True
        sup = getattr(impl, self.async_probe, None)
        if sup is None or not hasattr(impl, self.async_entry):
            return False
        try:
            return bool(sup())
        except Exception:
            return False

    def group_bytes(self, reqs) -> int:
        """Input bytes the router judges the group by."""
        return sum(r.nbytes for r in reqs)

    def form(self, key: Tuple, reqs):
        """Stack the group's requests ([B, width, chunk]): views for
        one, one concat for more.  Raises on a malformed payload."""
        n = self.width(key, reqs)
        return _cat([r.as_array(n) for r in reqs])

    def probe(self, b, twin, key: Tuple, reqs, stack) -> None:
        """What _cpu_rate times."""
        self.twin_call(twin, key, reqs, stack)

    def book(self, b, key: Tuple, reqs, nstripes: int) -> dict:
        """Count one device dispatch of the lane; -> keywords for its
        ``batcher.dispatch`` section."""
        return {}


class _EncLane(_Lane):
    name, prefix, group = "enc", "", "encode"
    counters = ("calls", "reqs_total", "cpu_reqs", "reqs_coalesced")
    concat_site = "batcher.batch_concat"
    async_entry = "encode_batch_async"
    fails_to_twin = False
    dispatch_event = twin_event = "ec:batch_dispatched"
    twin_is_call = False             # calls counts device calls only
    twin_perf = ("coalesced",)
    single_counts = ("reqs_total", "cpu_reqs", "cpu_calls")

    def geometry(self, key):
        return key[1:]               # the queue key is ("enc",) + it

    bucket = geometry                # encode's buckets carry no tag

    def width(self, key, reqs):
        return reqs[0].ec_impl.get_data_chunk_count()

    def launch(self, key, reqs, stack, lo, hi):
        return reqs[0].ec_impl.encode_batch_async(stack[lo:hi])

    def twin_call(self, impl, key, reqs, stack):
        return impl.encode_batch(stack)

    def probe(self, b, twin, key, reqs, stack):
        # encode's rate is the rate of its per-request fallback: the
        # twin's batched call AND the shard gathers behind it
        b._cpu_encode(reqs[0])

    def single(self, b, key, r):
        return b._cpu_encode(r)

    def split(self, b, key, reqs, result):
        """The full {shard: bytes} chunk map per rider: its own data
        columns and its rows of the [B, m, chunk] parity."""
        impl = reqs[0].ec_impl
        k = impl.get_data_chunk_count()
        m = impl.get_coding_chunk_count()
        off = 0
        for r in reqs:
            arr, p = r.as_array(k), result[off:off + r.nstripes]
            off += r.nstripes
            cols = {i: arr[:, i] for i in range(k)}
            cols.update({k + j: p[:, j] for j in range(m)})
            out, copied = _column_views(cols)
            if copied:
                b._note_copy(copied, "batcher.shard_gather")
            yield out


class _DecLane(_Lane):
    name, prefix, group = "dec", "dec_", "decode"
    counters = ("dec_calls", "dec_reqs", "dec_cpu_reqs",
                "dec_coalesced")
    crossover = "_dec_min_device_bytes"
    concat_site = "batcher.dec_batch_concat"
    async_entry = "decode_batch_async"
    async_probe = "decode_async_supported"
    # without it the group is routed at completion time, and a
    # device-bound one runs the plug-in's fenced decode_batch on its
    # own ec-dec-dev thread (_serve_sync)
    without_async = "sync"
    sync_call = True
    twin_books = False
    twin_perf = ("calls", "coalesced")
    single_counts = ("dec_reqs",)

    def group_bytes(self, reqs):
        cs = reqs[0].sinfo.chunk_size
        return sum(r.nstripes * cs * len(r.have) for r in reqs)

    def form(self, key, reqs):
        """One [B, cs] stack per surviving shard id (key[2])."""
        cs = reqs[0].sinfo.chunk_size
        return {s: _cat([ecutil.as_stripe_array(r.have[s], r.nstripes,
                                                1, cs)
                         .reshape(r.nstripes, cs) for r in reqs])
                for s in key[2]}

    def launch(self, key, reqs, stack, lo, hi):
        return reqs[0].ec_impl.decode_batch_async(
            {s: v[lo:hi] for s, v in stack.items()},
            reqs[0].sinfo.chunk_size)

    def twin_call(self, impl, key, reqs, stack):
        return impl.decode_batch(stack, reqs[0].sinfo.chunk_size)

    def single(self, b, key, r):
        return ecutil.decode(r.sinfo, r.ec_impl, r.have, set(r.want))

    def book(self, b, key, reqs, nstripes):
        """The plug-in's entry reconstructs every chunk absent from
        the have-set key[2] (tpu.decode_batch_async), the riders want
        the missing-set key[3]: chunk rows produced and asked for,
        and the signature if the process dispatches it for the first
        time."""
        out = (reqs[0].ec_impl.get_chunk_count() - len(key[2])) * nstripes
        wanted = len(key[3]) * nstripes
        b.dec_rows_out += out
        b.dec_rows_wanted += wanted
        cls = EncodeBatcher
        if key[1:] not in cls._dec_signatures:
            with cls._breaker_lock:
                if key[1:] not in cls._dec_signatures:
                    cls._dec_signatures.add(key[1:])
                    b.dec_signatures += 1
        return {"rows_out": out, "rows_wanted": wanted}

    def split(self, b, key, reqs, result):
        """{wanted shard: bytes} per rider: reconstructed shards (the
        erasure signature key[3]) from the batched result, wanted
        shards that were read directly passed through — the contract
        of ecutil.decode."""
        missing = key[3]
        off = 0
        for r in reqs:
            out = {}
            for s in r.want:
                if s in missing:
                    # row slice of a [B, cs] batch result; the
                    # memoryview rides downstream by reference
                    out[s] = memoryview(np.ascontiguousarray(
                        result[s][off:off + r.nstripes])).cast("B")
                else:
                    h = r.have[s]
                    out[s] = h if isinstance(h, bytes) else \
                        memoryview(h).cast("B")
            off += r.nstripes
            yield out


class _DeltaLane(_Lane):
    name, prefix, group = "delta", "delta_", "delta"
    counters = ("delta_calls", "delta_reqs", "delta_cpu_reqs",
                "delta_coalesced")
    crossover = "_delta_min_device_bytes"
    concat_site = "batcher.delta_batch_concat"
    async_entry = "delta_encode_batch_async"
    async_probe = "delta_async_supported"
    dispatch_event = "ec:delta_dispatched"
    single_counts = ("delta_reqs", "delta_cpu_reqs")

    def route_fields(self, key):
        return {"dirty_cols": len(key[2])}

    def width(self, key, reqs):
        return len(key[2])           # the dirty-column signature

    def launch(self, key, reqs, stack, lo, hi):
        return reqs[0].ec_impl.delta_encode_batch_async(
            stack[lo:hi], key[2])

    def twin_call(self, impl, key, reqs, stack):
        return impl.core.delta_parity(
            np.asarray(stack, dtype=np.uint8), key[2])

    def single(self, b, key, r):
        return b._delta_inline(r.ec_impl, r.sinfo, r.delta, key[2])

    def split(self, b, key, reqs, result):
        """{parity_shard: Δparity bytes} per rider from a [B, m,
        chunk] stack, to XOR into the stored parity (xor_write)."""
        k = reqs[0].ec_impl.get_data_chunk_count()
        off = copied = 0
        for r in reqs:
            p = result[off:off + r.nstripes]
            off += r.nstripes
            out, n = _column_views(
                {k + j: p[:, j] for j in range(p.shape[1])})
            copied += n
            yield out
        if copied:
            b._note_copy(copied, "batcher.delta_shard_gather")


# the ladder's verdicts (_route, _breaker_blocks)
_ROUTE_REASONS = (
    ("device", "batches over the crossover -> device"),
    ("pin", "batches under the operator/calibration pin -> twin "
            "(deterministic)"),
    ("learned", "batches under the LEARNED crossover -> twin"),
    ("idle_probe", "idle-device re-probes forced to the device"),
    ("tick_probe", "1-in-N periodic probes forced to the device"),
    ("breaker_open", "batches the open breaker routed to the twin"),
    ("breaker_probe", "re-admission probes through the open breaker"))

_ENC, _DEC, _DELTA = _EncLane(), _DecLane(), _DeltaLane()
_LANES: Dict[str, _Lane] = {lane.name: lane
                            for lane in (_ENC, _DEC, _DELTA)}


class EncodeBatcher:
    """Per-OSD encode coalescer (one collector thread).

    The CPU/device crossover and measured CPU rates are CLASS-level:
    the device and the link are machine properties, so every batcher
    in the process (one per OSD in test clusters; one per daemon in a
    real deployment) shares one learned estimate instead of each
    paying its own slow probe."""

    _cpu_bps: Dict[Tuple, float] = {}        # per geometry, shared
    _min_device_bytes: float = 0.0           # learned crossover, shared
    _pinned_min_device_bytes: float = 0.0    # operator pin (breaker
                                             # close resets TO this)
    _dec_min_device_bytes: float = 0.0       # decode-side crossover;
                                             # 0 = not yet learned ->
                                             # encode's (_min_bytes)
    _delta_min_device_bytes: float = 0.0     # parity-delta crossover,
                                             # seeded like decode's
    _probe_tick: int = 0                     # shared probe cadence
    _warmed: set = set()                     # geometries prewarmed
    _h2d_bps: float = 0.0                    # warm link rate EWMA, shared
    _dev_bps: Dict[Tuple, float] = {}        # steady-state device
                                             # throughput EWMA per
                                             # geometry (compile/outlier
                                             # rejection in the learner)
    # per-mesh-shape learner state (ISSUE 12): the crossover and link
    # EWMA model the AGGREGATE device+ICI bandwidth, so a dp x sp mesh
    # and a single chip must not share one estimate.  _mesh_key is the
    # (dp, sp) shape the CURRENT class-level scalars belong to (None =
    # single chip); _mesh_state stashes the scalars of every other
    # shape seen, swapped by _rekey_mesh when the live mesh changes.
    _mesh_key: Optional[Tuple] = None
    _mesh_state: Dict[Optional[Tuple], dict] = {}
    # shared idle clocks, seeded by the FIRST batcher construction
    # (None until then): seeding at import would treat process
    # lifetime as device idleness, while re-seeding on every
    # construction would reset the idle-reprobe clock for ALL
    # batchers each time a multi-OSD cluster builds another OSD
    _last_device_ts: Optional[float] = None     # last device activity
    _last_idle_probe_ts: Optional[float] = None
    # device circuit breaker — class-level like the crossover it
    # guards: the device is a machine property, so one OSD's string
    # of dispatch failures should route EVERY in-process batcher's
    # traffic to the CPU twin, not just its own
    _breaker_lock = threading.Lock()
    _breaker_failures: int = 0               # consecutive device errors
    _breaker_open: bool = False
    _breaker_opens: int = 0                  # cumulative open transitions
    _breaker_closes: int = 0                 # cumulative re-admissions
    # prewarm runs once per geometry per PROCESS, so what it met must
    # be visible from every OSD's dump_device
    _prewarm_errors: List[dict] = []
    PREWARM_ERRORS_CAP = 64
    # (geometry, have-set, missing-set) of every decode group some
    # batcher of the process has dispatched: a signature is a row set
    # of the one shared backend, so it is new once per process, and
    # the per-OSD ``dec_signatures`` sum to len() of this
    _dec_signatures: set = set()

    def __init__(self, conf=None, perf=None, perf_coll=None,
                 recorder=None, contention=None, daemon: str = ""):
        # whose batcher this is: the d= keyword of its threads' sections
        self.daemon = daemon

        def get(k, d):
            if conf is None:
                return d
            try:
                return conf[k]
            except KeyError:
                return d
        # kept for the live-tuning seam: apply_tuning() re-reads the
        # runtime-tunable knobs from here at safe points (collector
        # loop top + OSD tuner tick) instead of latching them forever
        self.conf = conf
        self.max_stripes = get("ec_tpu_batch_stripes", 1024)
        self.window_s = get("ec_tpu_queue_window_us", 200) / 1e6
        # admission-aware coalescing window: the effective window
        # (dyn_window_s) doubles while submits keep arriving at its
        # expiry (queue pressure -> bigger batches clear the device
        # crossover) and halves back toward the base once a window
        # closes with no new joiners (drained queue -> don't tax
        # latency).  tick_flush() remains the hard cut.
        wmax = get("ec_tpu_queue_window_max_us", 0)
        self.window_base_s = self.window_s
        self.window_max_s = (wmax / 1e6) if wmax > 0 \
            else max(self.window_s * 16, 0.02)
        self.dyn_window_s = self.window_s
        self.window_grows = 0        # admission extensions granted
        self.window_cuts = 0         # drain-driven shrinks
        self.last_queue_depth = 0    # requests in the last dispatch
        self.queue_depth_hwm = 0
        # encode-group occupancy (ISSUE 8): the biggest single group
        # dispatched, in requests and stripes — the shard-per-core
        # regression bar is "concurrent cluster writes coalesce into
        # >=k-stripe groups, not per-PG singletons"
        self.group_reqs_hwm = 0
        self.group_stripes_hwm = 0
        self.bytes_copied = 0        # full-payload copies inside the
                                     # batcher (gathers/concats)
        # adaptive CPU/device routing (ec_tpu_fallback_cpu): a device
        # call pays a fixed dispatch+transfer cost that can dwarf the
        # MXU win on small batches — especially over a slow link.  The
        # crossover is LEARNED: batches below the threshold encode on
        # the CPU twin; the threshold doubles when a device call loses
        # to the predicted CPU time and halves when it wins big.
        self.adaptive_cpu = get("ec_tpu_fallback_cpu", True)
        pin = get("ec_tpu_min_device_bytes", 0)
        if pin:
            # operator-pinned crossover: routing is deterministic from
            # the first op instead of riding the prewarm/learning race
            # (probes + big wins can still lower it at runtime).  The
            # pin is remembered separately so a circuit-breaker close
            # restores the OPERATOR's crossover, not whatever CPU bias
            # the learner accumulated while the device was sick.
            # Deliberately PROCESS-global even though the conf is per
            # instance: the crossover models the machine's device+link,
            # so in a multi-OSD process the last-constructed OSD's pin
            # wins (mixed per-OSD pins in one process are unsupported).
            EncodeBatcher._min_device_bytes = float(pin)
            EncodeBatcher._pinned_min_device_bytes = float(pin)
        self.probe_interval = get("ec_tpu_crossover_probe_interval", 16)
        # a device that served ZERO recent traffic gets re-probed
        # aggressively (one group per idle period) — the 1-in-N tick
        # probe alone starves on a lightly loaded OSD where small
        # batches would otherwise pin the CPU bias forever
        self.idle_reprobe_s = get("ec_tpu_device_idle_reprobe_s", 2.0)
        # collection/dispatch of window N+1 overlaps completion of
        # window N: dispatched groups hand off to a completion worker
        # through a bounded FIFO (depth = groups genuinely in flight
        # on the device; the blocking put is the throttle)
        self.inflight_groups = max(1, get("ec_tpu_inflight_groups", 2))
        # multichip mesh shape (ISSUE 12): 0 = auto (use every visible
        # JAX device, dp x sp factored by the backend); >1 forces the
        # device count, ec_tpu_mesh_sp forces the chunk-width axis.
        # The batcher only FORWARDS the shape — the backend owns mesh
        # construction and the sharded dispatch path.
        self.mesh_devices = get("ec_tpu_mesh_devices", 0)
        self.mesh_sp = get("ec_tpu_mesh_sp", 0)
        self._mesh_noted = False     # mesh_build drained to recorder
        # seed the shared idle clocks ONCE (first batcher built, not
        # at import and not per construction — see the class attrs)
        if EncodeBatcher._last_device_ts is None:
            EncodeBatcher._last_device_ts = time.monotonic()
            EncodeBatcher._last_idle_probe_ts = time.monotonic()
        self.crossover_min = get("ec_tpu_crossover_min_bytes", 64 << 10)
        self.device_error_threshold = get(
            "ec_tpu_device_error_threshold", 3)
        self.device_retry_s = get("ec_tpu_device_retry_ms", 2.0) / 1e3
        # device-phase stall threshold: an h2d or compute-fence phase
        # exceeding this flight-records a device_stall (+ rate-limited
        # auto-dump), mirroring the lock_stall path
        self.phase_stall_s = get(
            "ec_tpu_device_phase_stall_ms", 250.0) / 1e3
        self.prewarm_enabled = get("osd_ec_prewarm", True)
        self.cpu_reqs = 0                        # routed to CPU twin
        self.perf = perf
        # dedicated "ec_batcher" counter subsystem: per-stage
        # histograms + routing/transfer/compile counters, dumped via
        # the admin socket's perf dump and scraped by mgr prometheus
        self.bperf = None
        if perf_coll is not None:
            bp = perf_coll.create("ec_batcher")
            if "queue_wait_us" not in bp._types:
                bp.add_histogram(
                    "queue_wait_us",
                    [50, 100, 200, 500, 1000, 2000, 5000, 20000,
                     100000],
                    "per-request wait from submit to dispatch (us)")
                bp.add_histogram(
                    "batch_stripes",
                    [1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024],
                    "stripes per batched device/twin call")
                bp.add_histogram(
                    "dispatch_ms",
                    [0.5, 1, 2, 5, 10, 25, 50, 100, 250, 1000],
                    "fenced dispatch-to-parity latency (ms)")
                bp.add("h2d_bytes",
                       description="data bytes staged to the device")
                bp.add("d2h_bytes",
                       description="parity bytes fetched back")
                bp.add("device_reqs",
                       description="encode requests routed to device")
                bp.add("cpu_reqs",
                       description="encode requests routed to twin")
                bp.add("coalesced_reqs",
                       description="requests that shared a call")
                bp.add("compile_count",
                       description="JIT compiles paid (prewarm)")
                bp.add_time_avg("compile_seconds",
                                "seconds per JIT compile")
                bp.add("bytes_copied",
                       description="payload bytes copied inside the "
                                   "batcher (shard gathers/concats)")
                bp.add("ec_encode_errors",
                       description="encode/continuation failures "
                                   "(each fails its rider ops with "
                                   "EIO rather than hanging them)")
                bp.add("device_errors",
                       description="classified device dispatch/"
                                   "completion failures (post-retry)")
                bp.add("breaker_open",
                       description="circuit-breaker open transitions "
                                   "(device -> CPU twin routing)")
                bp.add("breaker_close",
                       description="circuit-breaker re-admissions "
                                   "(successful probe closed it)")
            self.bperf = bp
        # flight recorder (utils/flight_recorder.py): every routing
        # verdict / breaker transition / staging stall / encode error
        # appends one ring event; None under unit-test stubs
        self.recorder = recorder
        # decode-side device-fault hook (OSD wires this to the SLO
        # engine's recovery-class error feed): called once per
        # classified decode device failure, after the CPU-twin
        # fallback is already queued.  Must not raise.
        self.on_decode_fault = None
        # "ec_device" perf subsystem — the device-side telemetry PR 5
        # shipped without: crossover routing verdicts BY REASON,
        # StagingPool ring occupancy/stall-grows, h2d link EWMA,
        # inflight-group depth, breaker state.  The timer-wheel
        # fire-lag histogram lives here too (filled by the OSD's
        # wheel callback) so one subsystem answers "what did the
        # device machinery do" in perf dump / prometheus.
        self.dperf = None
        if perf_coll is not None:
            dp = perf_coll.create("ec_device")
            if "route_device" not in dp._types:
                from ..utils.perf import TYPE_U64
                for g, desc in (
                        ("staging_hits", "stagings served from a "
                                         "reused ring slot"),
                        ("staging_allocs", "staging arrays ever "
                                           "allocated"),
                        ("staging_stall_allocs", "ring grows after "
                                                 "an acquire stall"),
                        ("staging_slots", "staging slots live across "
                                          "all shape rings"),
                        ("staging_in_flight", "staging slots checked "
                                              "out right now"),
                        ("h2d_bps", "h2d link bandwidth EWMA "
                                    "(bytes/s, fenced samples)"),
                        ("inflight_groups_now", "encode groups in "
                                                "flight on the "
                                                "device"),
                        ("inflight_groups_hwm", "high-water mark of "
                                                "in-flight encode "
                                                "groups"),
                        ("breaker_open_now", "device circuit breaker "
                                             "state (1=open)")):
                    dp.add(g, TYPE_U64, desc)
                dp.add("breaker_opened",
                       description="breaker open transitions")
                dp.add("breaker_closed",
                       description="breaker close (re-admission) "
                                   "transitions")
                dp.add_histogram(
                    "timer_fire_lag_us",
                    [100, 500, 1000, 5000, 10000, 25000, 50000,
                     100000, 500000],
                    "timer-wheel fire lag vs requested deadline (us)")
            # routing verdicts BY REASON, one ladder's worth per lane
            # (each under its own guard: dperf instances created by
            # older sessions predate the later lanes' counters)
            for lane in _LANES.values():
                for reason, desc in _ROUTE_REASONS:
                    name = f"{lane.prefix}route_{reason}"
                    if name not in dp._types:
                        dp.add(name, description=f"{lane.group} "
                               f"routing verdicts: {desc}")
            if "staging_host_bytes_now" not in dp._types:
                # memory-accounting + overlap gauges (ISSUE 10),
                # registered under their own guard: dperf instances
                # created by older sessions predate these
                from ..utils.perf import TYPE_U64
                for g, desc in (
                        ("staging_host_bytes_now", "host staging ring "
                                                   "footprint (bytes)"),
                        ("staging_host_bytes_peak", "peak host staging "
                                                    "ring footprint"),
                        ("dev_matrix_bytes_now", "device-resident "
                                                 "coding matrix bytes "
                                                 "(per-geometry cache)"),
                        ("compile_cache_entries", "compiled-executable "
                                                  "cache occupancy"),
                        ("pipeline_overlap_frac", "fraction of window "
                                                  "wall where group "
                                                  "N+1 h2d overlaps "
                                                  "group N compute")):
                    dp.add(g, TYPE_U64, desc)
                dp.add("device_phase_stalls",
                       description="device phases (h2d / compute "
                                   "fence) that exceeded "
                                   "ec_tpu_device_phase_stall_ms")
            if "mesh_dp" not in dp._types:
                # multichip mesh shape gauges (ISSUE 12), own guard:
                # dperf instances created by older sessions predate
                # these
                from ..utils.perf import TYPE_U64
                for g, desc in (
                        ("mesh_dp", "stripe-batch (dp) axis of the "
                                    "active device mesh (0 = single "
                                    "chip)"),
                        ("mesh_sp", "chunk-width (sp) axis of the "
                                    "active device mesh"),
                        ("mesh_devices", "devices in the active "
                                         "encode/decode mesh")):
                    dp.add(g, TYPE_U64, desc)
            self.dperf = dp
        # device-phase ledger accumulator (utils/device_ledger):
        # per-group stage_acquire..deliver stamps harvested from each
        # AsyncBatch at completion, plus the overlap engine over its
        # recent ring.  Dumped via the OSD's dump_device command and
        # bench's device_waterfall block.
        self.ledger_accum = DeviceLedgerAccum(perf_coll)
        self._ledger_completions = 0
        self._last_backend = None    # codec backend seen at completion
        self._route_reason = None    # last verdict's reason code
        self._staging_stalls_seen = 0
        self._inflight_hwm = 0
        # cumulative per-stage attribution (seconds of request time
        # spent in each pipeline stage; consumed by bench.py's
        # time-attribution line).  Collector-thread writes only.
        self.stage_seconds = {"queue_wait": 0.0, "batch_form": 0.0,
                              "h2d": 0.0, "device": 0.0, "d2h": 0.0}
        self.compile_count = 0
        self.compile_seconds = 0.0
        # collector wakeup condition, wait-time instrumented when the
        # OSD supplies its contention sink (utils/locks.py)
        from ..utils.locks import TimedCondition
        self._cond = TimedCondition("batcher_cond", stats=contention)
        self._queues: Dict[Tuple, List] = {}
        self._pending_stripes = 0
        self._first_enqueue = 0.0
        self._flush_now = False      # tick_flush(): cut the window
        self._stop = False
        # introspection (tested + surfaced via perf counters)
        self.calls = 0               # batched encode calls issued
        self.reqs_total = 0          # requests encoded
        self.reqs_coalesced = 0      # requests that shared a call
        self.cpu_calls = 0           # batched encode calls on the twin
        self.dec_calls = 0           # batched decode calls issued
        self.dec_reqs = 0            # decode requests served
        self.dec_coalesced = 0       # decode requests that shared a call
        self.dec_cpu_reqs = 0        # decode requests on the CPU twin
        self.dec_signatures = 0      # erasure signatures (have/missing
                                     # pairs) this batcher was the
                                     # process's first to dispatch
        self.dec_rows_out = 0        # chunk rows decode dispatches
                                     # produced (every absent chunk id
                                     # times the stripes) ...
        self.dec_rows_wanted = 0     # ... and of those, rows a rider
                                     # asked for
        self.delta_calls = 0         # batched parity-delta calls issued
        self.delta_reqs = 0          # delta requests served
        self.delta_coalesced = 0     # delta requests that shared a call
        self.delta_cpu_reqs = 0      # delta requests on the CPU twin
        self.encode_errors = 0       # encode/continuation failures
        self.device_errors = 0       # classified device failures
        self.last_device_error: Optional[str] = None
        self._cpu_twins: Dict[Tuple, object] = {}  # device-failure path
        self._dec_threads: List[threading.Thread] = []
        # completion worker: joins dispatched groups in FIFO order so
        # the collector can collect/dispatch the NEXT window while
        # this window's parity is still in flight (segment N+1's h2d
        # overlaps segment N's fanout)
        self._completions: "queue.Queue" = queue.Queue(
            maxsize=self.inflight_groups)
        self._comp_thread = threading.Thread(
            target=self._completion_loop, name="ec-batcher-join",
            daemon=True)
        self._comp_thread.start()
        self._thread = threading.Thread(target=self._run,
                                        name="ec-batcher", daemon=True)
        self._thread.start()

    # -- producer side ---------------------------------------------------
    def submit(self, ec_impl, sinfo: ecutil.StripeInfo, data: bytes,
               cb: Callable[[Dict[int, bytes]], None],
               tracked=None) -> None:
        """Queue one aligned extent for encoding; ``cb`` receives the
        full {shard: bytes} chunk map (data + parity) later, from the
        collector thread.  ``tracked`` is an optional OpTracker handle
        that receives batcher stage events.  Codecs without the
        batched async API don't benefit from coalescing — they encode
        inline."""
        with section("batcher.submit", lane="enc",
                     bytes=ecutil.nbytes_of(data)):
            if self._stop or not hasattr(ec_impl, "encode_batch_async"):
                cb(ecutil.encode(sinfo, ec_impl, data))
                return
            req = _Req(ec_impl, sinfo, data, cb, tracked)
            if req.nstripes == 0:
                cb({i: b"" for i in range(ec_impl.get_chunk_count())})
                return
            if not self._enqueue(
                    ("enc",) + _geometry_key(ec_impl, sinfo), req):
                cb(ecutil.encode(sinfo, ec_impl, data))

    def submit_decode(self, ec_impl, sinfo: ecutil.StripeInfo,
                      have: Dict[int, bytes], want,
                      cb: Callable[[Optional[Dict[int, bytes]]], None]
                      ) -> None:
        """Queue a batched reconstruction of ``want - have`` shard
        chunks; ``cb`` later receives {missing_shard: bytes} (or None
        on failure) from the collector thread.

        Decode requests coalesce per (geometry, erasure signature):
        recovery after an OSD loss hammers ONE signature for the whole
        rebuild (every object lost the same shard), which makes it the
        best possible coalescing customer — the reference decodes each
        object's recovery window separately on the submitting thread
        (reference src/osd/ECBackend.cc:414-481
        handle_recovery_read_complete)."""
        with section("batcher.submit", lane="dec"):
            missing = set(want) - set(have)
            if not missing:
                # everything wanted was read directly (e.g. a stray held
                # the 'missing' shard): passthrough, like ecutil.decode
                cb({s: (have[s] if isinstance(have[s], bytes)
                        else memoryview(have[s]).cast("B"))
                    for s in want})
                return
            queued = not self._stop and hasattr(ec_impl, "decode_batch")
            if queued:
                req = _DecReq(ec_impl, sinfo, have, want, cb)
                if req.nstripes == 0:
                    cb({s: b"" for s in want})
                    return
                queued = self._enqueue(
                    ("dec", _geometry_key(ec_impl, sinfo),
                     tuple(sorted(have)), tuple(sorted(missing))), req)
            if not queued:
                try:
                    dec = ecutil.decode(sinfo, ec_impl, have, set(want))
                except Exception:
                    dec = None
                cb(dec)

    def submit_delta(self, ec_impl, sinfo: ecutil.StripeInfo, delta,
                     dirty_cols,
                     cb: Callable[[Optional[Dict[int, bytes]]], None],
                     tracked=None) -> None:
        """Queue a parity-delta encode for a partial-stripe
        overwrite: ``delta`` is old XOR new chunk bytes for the DIRTY
        data columns only ([nstripes, D, chunk] layout); ``cb`` later
        receives {parity_shard_index: Δparity bytes} (or None on
        failure) from the collector thread — the caller XORs each
        Δparity into the stored parity chunk (``xor_write``).

        Delta requests coalesce per (geometry, dirty-column
        signature): a sub-stripe overwrite workload re-hits few
        signatures (a 4 KiB write always dirties one column), so hot
        small-write traffic lands on a handful of prewarmed compiled
        shapes — the same coalescing economics as recovery."""
        with section("batcher.submit", lane="delta",
                     bytes=ecutil.nbytes_of(delta)):
            cols = tuple(sorted(dirty_cols))
            queued = not self._stop and \
                hasattr(ec_impl, "delta_encode_batch_async")
            if queued:
                req = _DeltaReq(ec_impl, sinfo, delta, cols, cb, tracked)
                if req.nstripes == 0:
                    k = ec_impl.get_data_chunk_count()
                    m = ec_impl.get_coding_chunk_count()
                    cb({k + j: b"" for j in range(m)})
                    return
                queued = self._enqueue(
                    ("delta", _geometry_key(ec_impl, sinfo), cols), req)
            if not queued:
                try:
                    out = self._delta_inline(ec_impl, sinfo, delta, cols)
                except Exception:
                    out = None
                cb(out)

    def _enqueue(self, key: Tuple, req) -> bool:
        """Queue one request under its group key and wake the
        collector.  False when the batcher has stopped (a submit that
        raced shutdown): the caller serves the request inline."""
        with self._cond:
            if self._stop:
                return False
            if not self._queues:
                self._first_enqueue = time.monotonic()
            self._queues.setdefault(key, []).append(req)
            self._pending_stripes += req.nstripes
            self._cond.notify()
        return True

    def _delta_inline(self, ec_impl, sinfo: ecutil.StripeInfo,
                      delta, cols) -> Dict[int, memoryview]:
        """Synchronous device-free Δparity (shutdown/no-async-API
        fallback for submit_delta)."""
        cs = sinfo.chunk_size
        nstripes = ecutil.nbytes_of(delta) // (len(cols) * cs)
        arr = np.asarray(ecutil.as_stripe_array(
            delta, nstripes, len(cols), cs), dtype=np.uint8)
        if hasattr(ec_impl, "delta_encode_batch"):
            parity = ec_impl.delta_encode_batch(arr, cols)
        else:
            parity = ec_impl.core.delta_parity(arr, cols)
        k = ec_impl.get_data_chunk_count()
        return {k + j: memoryview(
                    np.ascontiguousarray(parity[:, j])).cast("B")
                for j in range(parity.shape[1])}

    def tick_flush(self) -> None:
        """Cut the coalescing window NOW: everything queued dispatches
        as one group set without waiting out ``window_s``.  The crimson
        reactor calls this at the end of each event-loop tick — every
        stripe submitted by ops processed in the tick has already
        joined the queue, so waiting longer buys no extra coalescing,
        only latency (the classic OSD has no such natural barrier and
        must rely on the time window).  No-op when nothing is queued."""
        with self._cond:
            if self._queues and not self._flush_now:
                self._flush_now = True
                self._cond.notify()

    def prewarm(self, ec_impl, sinfo: ecutil.StripeInfo) -> None:
        """Pay the pool geometry's one-time costs at backend-build
        time instead of on the first client op (the reference pays GF
        table setup at plugin load — jerasure_init.cc:37, preloaded at
        global_init.cc:600): measure the CPU twin's rate for the
        crossover router, and compile the device kernels for the
        batch shapes the coalescer dispatches.  Background thread —
        OSD boot is not stalled; a first op racing the warm simply
        shares the in-progress compile (ChainLRU in-progress marker).
        Once per geometry process-wide."""
        if not self.prewarm_enabled or \
                not hasattr(ec_impl, "encode_batch_async"):
            return
        # configure the backend's device mesh BEFORE any learner
        # seeding: the h2d EWMA and crossover thresholds are keyed per
        # mesh shape (_rekey_mesh), so the seed measurements below
        # must accrue to the shape real dispatches will ride.  An
        # explicit ec_tpu_mesh_sp that cannot shard raises HERE (via
        # the backend's strict prewarm_geometry), not mid-dispatch.
        backend = getattr(getattr(ec_impl, "core", None),
                          "backend", None)
        if backend is not None and hasattr(backend, "configure_mesh"):
            backend.configure_mesh(self.mesh_devices, self.mesh_sp)
            self._note_mesh(backend)
        key = _geometry_key(ec_impl, sinfo)
        with self._cond:
            if key in EncodeBatcher._warmed:
                return
            EncodeBatcher._warmed.add(key)

        def work():
            try:
                # the probe must be REPRESENTATIVE: a tiny buffer
                # under-measures the CPU twin (per-stripe call
                # overhead dominates), which makes device round trips
                # look competitive and mis-routes real batches
                nprobe = max(64, min(self.max_stripes, 256))
                probe = _Req(ec_impl, sinfo,
                             b"\0" * (sinfo.stripe_width * nprobe),
                             lambda _c: None)
                qkey = (_ENC.name,) + key
                self._cpu_rate(_ENC, qkey, [probe])
                import jax
                if jax.default_backend() == "cpu":
                    return       # XLA:CPU compiles these in
                                 # milliseconds on first use
                k = ec_impl.get_data_chunk_count()
                for nb in sorted({max(1, self.max_stripes),
                                  max(1, self.max_stripes // 2)}):
                    if self._stop:
                        return
                    z = np.zeros((nb, k, sinfo.chunk_size),
                                 dtype=np.uint8)
                    if EncodeBatcher._h2d_bps <= 0:
                        # seed the link estimate from a WARM transfer:
                        # the first device_put pays allocator/runtime
                        # warmup that is NOT link cost — timing it
                        # under-states the link by an order of
                        # magnitude and poisons the h2d/device/d2h
                        # split AND the overlap model's bottleneck
                        # leg.  Transfer once cold (discarded), time
                        # the second.  Real batches keep updating the
                        # EWMA afterwards (staging-pool samples).
                        jax.block_until_ready(jax.device_put(z))
                        t0 = time.monotonic()
                        jax.block_until_ready(jax.device_put(z))
                        EncodeBatcher._h2d_bps = z.nbytes / max(
                            time.monotonic() - t0, 1e-9)
                    t0 = time.monotonic()
                    ec_impl.encode_batch_async(z).wait()  # compile
                    dt = time.monotonic() - t0
                    self.compile_count += 1
                    self.compile_seconds += dt
                    if self.bperf is not None:
                        self.bperf.inc("compile_count")
                        self.bperf.tinc("compile_seconds", dt)
                    # SEED the crossover from a second, POST-compile
                    # call (timing the first would fold seconds of
                    # jit into the estimate and misroute a healthy
                    # device to the CPU twin): on a slow device link
                    # the very first client op must already route to
                    # the CPU twin instead of waiting out a doomed
                    # round trip
                    t0 = time.monotonic()
                    parity = ec_impl.encode_batch_async(z).wait()
                    self._learn_crossover(
                        _ENC, qkey, [probe], time.monotonic() - t0,
                        z.nbytes, parity.nbytes, trust_win=False)
            except Exception as e:
                # the daemon stays up, but a geometry that cannot
                # compile or dispatch has to be known BEFORE the first
                # client op meets it inside a dispatch, where the
                # breaker would turn it into quiet twin traffic
                self.note_prewarm_error("batcher.prewarm", e)
        threading.Thread(target=work, name="ec-prewarm",
                         daemon=True).start()

    def stop(self, drain: float = 30.0) -> None:
        """Stop the collector, draining in-flight device work first
        (up to ``drain`` seconds) so no continuation lands after the
        caller unmounts the store.  Idle batchers return instantly."""
        with self._cond:
            self._stop = True
            self._cond.notify()
        deadline = time.monotonic() + max(drain, 0.1)
        self._thread.join(timeout=max(drain, 0.1))
        # the collector queued a sentinel on exit; the completion
        # worker drains every in-flight group behind it, then stops
        self._comp_thread.join(
            timeout=max(0.1, deadline - time.monotonic()))
        for t in self._dec_threads:
            t.join(timeout=max(0.0, deadline - time.monotonic()))

    def _note_copy(self, nbytes: int, site: str) -> None:
        self.bytes_copied += nbytes
        copytrack.note_copy(nbytes, site)
        if self.bperf is not None:
            self.bperf.inc("bytes_copied", nbytes)

    # -- collector -------------------------------------------------------
    def apply_tuning(self) -> None:
        """Re-read the runtime-tunable knobs from conf and apply them
        to the LIVE pipeline — no restart, bit-exact output (the
        knobs only shape batching/overlap, never data).  Called at
        the top of every collector cycle and from the OSD tuner tick,
        so a ``conf.set(..., source="runtime")`` (operator or
        autotuner) lands within one window:

        * ``ec_tpu_queue_window_max_us`` — coalescing-window ceiling;
          the dynamic window is re-clamped under ``_cond``.
        * ``ec_tpu_inflight_groups`` — the bounded completion FIFO's
          depth; ``queue.Queue`` checks ``maxsize`` under its own
          mutex on every put, so resizing it there (+ waking blocked
          putters) is the safe seam.
        * ``ec_tpu_staging_depth`` — forwarded to the codec backend's
          StagingPool (jax_engine) when one has been seen.
        """
        conf = self.conf
        if conf is None:
            return
        def get(k, d):
            try:
                return conf[k]
            except Exception:
                return d
        wmax = get("ec_tpu_queue_window_max_us", None)
        if wmax is not None:
            new_max = (wmax / 1e6) if wmax > 0 \
                else max(self.window_base_s * 16, 0.02)
            if new_max != self.window_max_s:
                with self._cond:
                    self.window_max_s = new_max
                    self.dyn_window_s = max(
                        min(self.dyn_window_s, new_max),
                        min(self.window_base_s, new_max))
        infl = get("ec_tpu_inflight_groups", None)
        if infl is not None:
            infl = max(1, int(infl))
            if infl != self.inflight_groups:
                self.inflight_groups = infl
                q = self._completions
                with q.mutex:
                    q.maxsize = infl
                    q.not_full.notify_all()
        depth = get("ec_tpu_staging_depth", None)
        backend = self._last_backend
        if depth is not None and backend is not None and \
                hasattr(backend, "configure_staging"):
            try:
                backend.configure_staging(int(depth))
            except Exception:
                pass

    def _run(self) -> None:
        while True:
            grew = False
            self.apply_tuning()
            with self._cond:
                while not self._queues and not self._stop:
                    self._cond.wait()
                if not self._queues and self._stop:
                    break       # sentinel queued below, OUTSIDE _cond
                # linger for the (admission-aware) window so concurrent
                # ops can join, unless the stripe budget is already met
                deadline = self._first_enqueue + self.dyn_window_s
                hard = self._first_enqueue + self.window_max_s
                seen = self._pending_stripes
                while (not self._stop and not self._flush_now
                       and self._pending_stripes < self.max_stripes):
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        if self._pending_stripes > seen \
                                and deadline < hard:
                            # submits kept arriving: extend by one base
                            # window (bounded by window_max_s) and widen
                            # the next cycle's opening window
                            grew = True
                            self.window_grows += 1
                            seen = self._pending_stripes
                            self.dyn_window_s = min(
                                self.dyn_window_s * 2,
                                self.window_max_s)
                            deadline = min(
                                time.monotonic() + self.window_base_s,
                                hard)
                            continue
                        break
                    self._cond.wait(remaining)
                if self._flush_now or not grew:
                    # the queue drained inside the window (or the
                    # reactor tick cut it): shrink back toward the base
                    nw = max(self.window_base_s, self.dyn_window_s / 2)
                    if nw < self.dyn_window_s:
                        self.window_cuts += 1
                        self.dyn_window_s = nw
                queues, self._queues = self._queues, {}
                depth = sum(len(v) for v in queues.values())
                self.last_queue_depth = depth
                if depth > self.queue_depth_hwm:
                    self.queue_depth_hwm = depth
                self._pending_stripes = 0
                self._flush_now = False
            # dispatch EVERY group's device call before joining any:
            # h2d staging + MXU compute of group B overlap group A's
            # parity d2h and continuations (same double buffering the
            # bench uses).  Joins then run on the completion worker —
            # the collector immediately loops back to collect the
            # NEXT window, so up to ``inflight_groups`` encode groups
            # genuinely overlap (segment N+1's h2d during segment N's
            # fanout); the bounded queue's blocking put is the
            # throttle.
            groups = []
            with section("batcher.form", d=self.daemon,
                         groups=len(queues), reqs=depth):
                for key, reqs in queues.items():
                    if len(reqs) > self.group_reqs_hwm:
                        self.group_reqs_hwm = len(reqs)
                    gstripes = sum(r.nstripes for r in reqs)
                    if gstripes > self.group_stripes_hwm:
                        self.group_stripes_hwm = gstripes
                    # every lane routes + dispatches HERE: the async
                    # handle rides the one bounded completion queue, so
                    # each honors ec_tpu_inflight_groups and pipelines
                    # its h2d under the previous group's compute.  The
                    # handle is the in-flight tiles (None: the dispatch
                    # failed), "twin", or "sync" (_Lane.without_async)
                    lane = _LANES[key[0]]
                    if lane.has_async(reqs[0].ec_impl):
                        to_cpu = self._route(lane, key, reqs)
                        self._note_route(lane, key, reqs, to_cpu)
                        handle = "twin" if to_cpu \
                            else self._dispatch(lane, key, reqs)
                    else:
                        handle = lane.without_async
                    groups.append((lane, key, reqs, handle))
            for group in groups:
                self._completions.put(group + (len(groups),))
                if self.dperf is not None:
                    depth = self._completions.qsize()
                    self.dperf.set("inflight_groups_now", depth)
                    if depth > self._inflight_hwm:
                        self._inflight_hwm = depth
                        self.dperf.set("inflight_groups_hwm", depth)
        # shutdown: queue the completion-worker sentinel with _cond
        # RELEASED — _completions is bounded, and a blocking put while
        # holding the cond would deadlock against any continuation
        # that re-enters submit()/flush() (which take _cond)
        self._completions.put(None)   # worker: drain + exit

    def _completion_loop(self) -> None:
        """FIFO join of dispatched groups (continuations preserve
        submission order — the contract ECBackend::check_ops needs).
        A continuation that raises must not kill the worker — that
        would wedge every EC write on the OSD — so each group is
        fault-isolated to its own ops."""
        while True:
            item = self._completions.get()
            if item is None:
                return
            lane, key, reqs, handle, ngroups = item
            with section("batcher.complete", d=self.daemon,
                         lane=lane.name, reqs=len(reqs)):
                try:
                    if handle == "twin":
                        self._twin(lane, key, reqs)
                    elif handle == "sync":
                        self._serve_sync(lane, key, reqs)
                    else:
                        self._join(lane, key, reqs, handle,
                                   trust_win=(ngroups == 1))
                except Exception:
                    # fail every rider op that has not completed yet: a
                    # worker-level fault must surface as EIO on the
                    # affected ops, never as a hang
                    self._cb_error(reqs)

    def _min_bytes(self, lane: _Lane) -> float:
        """The lane's crossover threshold: its own once its groups
        have taught it one, encode's until then (_Lane.crossover)."""
        cls = EncodeBatcher
        return getattr(cls, lane.crossover) or cls._min_device_bytes

    def _route(self, lane: _Lane, key: Tuple, reqs: List) -> bool:
        """True when the group goes to the CPU twin: the learned
        crossover says it is too small to pay the device round trip,
        or the open breaker blocks it.  One ladder for the three
        lanes, with a shared probe cadence and shared idle clocks —
        the device is one machine property; the verdict's reason is
        left in _route_reason for _note_route."""
        thr = self._min_bytes(lane) if self.adaptive_cpu else 0
        if thr <= 0 or lane.group_bytes(reqs) >= thr:
            self._route_reason = "device"
            return self._breaker_blocks()
        # idle re-probe: a device that served ZERO traffic for a
        # whole idle period gets one group as a probe IMMEDIATELY —
        # a learned CPU bias with no device activity behind it is
        # exactly the misrouting failure mode (every encode on the
        # twin, crossover never challenged), and on a lightly loaded
        # OSD the 1-in-N tick below may take minutes to fire.  Rate
        # limited to one probe per idle period so an actually-slow
        # device is not hammered.
        #
        # A crossover sitting AT (or under) an operator/calibration
        # pin is not learned bias — it is the measured answer for
        # this machine, and the pin's contract is DETERMINISTIC
        # routing (see __init__) — so below-pin groups take the twin
        # with no probe taxes at all; only a threshold the LEARNER
        # pushed above the pin (or learned from scratch) gets
        # challenged by the idle/tick probes below.
        cls = EncodeBatcher
        if 0 < cls._pinned_min_device_bytes and \
                thr <= cls._pinned_min_device_bytes:
            self._route_reason = "pin"
            return True
        now = time.monotonic()
        if self.idle_reprobe_s > 0 and \
                now - cls._last_device_ts > self.idle_reprobe_s and \
                now - cls._last_idle_probe_ts > self.idle_reprobe_s:
            cls._last_idle_probe_ts = now
            self._route_reason = "idle_probe"
            return self._breaker_blocks()
        # periodic probe: route an occasional small batch to the
        # device anyway so the threshold can come back down when the
        # link/device recovers.  The tick is class-level like the
        # crossover it refreshes: 13 in-process OSDs share ONE
        # learned threshold, so they should share one probe cadence
        # instead of each paying its own 1-in-N device round trips
        # (per-instance ticks also mean a primary seeing few ops
        # never probes at all)
        cls._probe_tick += 1
        if cls._probe_tick % self.probe_interval != 0:
            self._route_reason = "learned"
            return True
        self._route_reason = "tick_probe"
        return self._breaker_blocks()
    def _breaker_blocks(self) -> bool:
        """True when the open circuit breaker routes this encode
        group to the coalesced CPU twin.  Rides the shared probe tick
        so 1-in-``probe_interval`` groups still reach the device as
        re-admission probes — a probe that completes closes the
        breaker (_device_success)."""
        if not EncodeBatcher._breaker_open:
            return False
        EncodeBatcher._probe_tick += 1
        blocked = EncodeBatcher._probe_tick % self.probe_interval != 0
        self._route_reason = "breaker_open" if blocked \
            else "breaker_probe"
        return blocked

    def _note_route(self, lane: _Lane, key: Tuple, reqs: List,
                    to_cpu: bool, record: bool = True) -> None:
        """Publish one routing verdict: reason-coded counter in the
        ec_device subsystem + one flight-recorder event.  Consumes
        _route_reason, so one group's reason cannot leak into the
        next's.  No locking beyond the perf counters' own."""
        reason = self._route_reason or \
            ("learned" if to_cpu else "device")
        self._route_reason = None
        name = f"{lane.prefix}route_{reason}"
        if self.dperf is not None and name in self.dperf._types:
            self.dperf.inc(name)
        rec = self.recorder
        if rec is not None and record:
            rec.note(f"{lane.prefix}route", reason=reason,
                     to="cpu" if to_cpu else "device",
                     bytes=lane.group_bytes(reqs), reqs=len(reqs),
                     crossover=int(self._min_bytes(lane)),
                     **lane.route_fields(key))
    def note_prewarm_error(self, where: str, exc: BaseException) -> None:
        """A prewarm (compile + first dispatch of a pool geometry)
        failed: log it with its traceback, flight-record it, and keep
        it for ``dump_device``."""
        derr_once("tpu", f"prewarm {where}", exc)
        with EncodeBatcher._breaker_lock:
            if len(EncodeBatcher._prewarm_errors) < \
                    self.PREWARM_ERRORS_CAP:
                EncodeBatcher._prewarm_errors.append(
                    {"where": where, "error": repr(exc),
                     "ts": time.time()})
        if self.recorder is not None:
            self.recorder.note("prewarm_error", where=where,
                               error=repr(exc))

    def _device_failure(self, kind: str,
                        exc: Optional[BaseException] = None) -> None:
        """Record one classified device failure (post-retry) with the
        exception that caused it; opens the breaker after
        ``ec_tpu_device_error_threshold`` consecutive failures."""
        self.device_errors += 1
        if exc is not None:
            self.last_device_error = f"{kind}: {exc!r}"
            derr_once("tpu", f"device {kind}", exc)
        if self.bperf is not None:
            self.bperf.inc("device_errors")
        opened = False
        cls = EncodeBatcher
        with cls._breaker_lock:
            cls._breaker_failures += 1
            if not cls._breaker_open and \
                    cls._breaker_failures >= self.device_error_threshold:
                cls._breaker_open = True
                cls._breaker_opens += 1
                opened = True
        rec = self.recorder
        if rec is not None:
            rec.note("device_error", error=kind,
                     exc=None if exc is None else repr(exc),
                     failures=cls._breaker_failures,
                     breaker_opened=opened)
        if kind == "decode":
            hook = self.on_decode_fault
            if hook is not None:
                try:
                    hook()
                except Exception:
                    pass             # telemetry must not kill decode
        if opened:
            if self.bperf is not None:
                self.bperf.inc("breaker_open")
            if self.dperf is not None:
                self.dperf.inc("breaker_opened")
                self.dperf.set("breaker_open_now", 1)
            # breaker-open is an incident: dump the recent routing/
            # error evidence while it is still in the ring
            if rec is not None:
                rec.note("breaker", state="open", cause=kind)
                rec.auto_dump("breaker-open")

    def _device_success(self) -> None:
        """A device call completed: clear the consecutive-failure
        run; if this was a probe through an open breaker, re-admit
        the device."""
        cls = EncodeBatcher
        cls._last_device_ts = time.monotonic()
        if not cls._breaker_failures and not cls._breaker_open:
            return                   # hot path: nothing to clear
        closed = False
        with cls._breaker_lock:
            cls._breaker_failures = 0
            if cls._breaker_open:
                cls._breaker_open = False
                cls._breaker_closes += 1
                closed = True
        if closed:
            # re-admission must come with FRESH routing stats: while
            # the breaker was open every group encoded on the twin
            # and the learner could only accumulate CPU bias, so the
            # crossover snaps back to the operator's pin (or fully
            # unlearned) and the device gets re-tried on its merits
            cls._min_device_bytes = cls._pinned_min_device_bytes
            cls._dec_min_device_bytes = 0.0   # re-seed from encode
            cls._delta_min_device_bytes = 0.0
            cls._dev_bps = {}
            if self.bperf is not None:
                self.bperf.inc("breaker_close")
            if self.dperf is not None:
                self.dperf.inc("breaker_closed")
                self.dperf.set("breaker_open_now", 0)
            if self.recorder is not None:
                self.recorder.note("breaker", state="closed",
                                   crossover=int(
                                       cls._min_device_bytes))

    def _deliver(self, r, out) -> None:
        """Run one rider's continuation; a failing continuation
        affects only its own op."""
        with section("batcher.deliver", lane=r.lane,
                     stripes=r.nstripes) as sec:
            try:
                r.done = True
                r.cb(out)
            except Exception as e:
                sec.set_metadata(error=type(e).__name__)
                self._cb_error()

    def _cb_error(self, reqs=None) -> None:
        """Report a continuation/encode failure.  During shutdown the
        op is already dead (teardown races deliver into an unmounting
        OSD — e.g. 'store not mounted'), so stay quiet rather than
        spraying tracebacks over the console and bench output.

        When ``reqs`` is given, every request that has not seen its
        callback yet gets ``cb(None)`` so its write op fails with EIO
        back through the EC backend instead of hanging until the
        client op timeout."""
        if not self._stop:
            traceback.print_exc()
            self.encode_errors += 1
            if self.bperf is not None:
                self.bperf.inc("ec_encode_errors")
            # a client op is about to die with EIO — flight-record
            # the failure and dump the evidence around it (the chaos
            # soak's "client error" incident trigger)
            if self.recorder is not None:
                self.recorder.note("encode_error",
                                   reqs=len(reqs or ()))
                self.recorder.auto_dump("client-encode-error")
        for r in (reqs or ()):
            if r.done:
                continue
            r.done = True
            try:
                r.cb(None)
            except Exception:
                pass                 # op teardown races

    @classmethod
    def reset_learning(cls) -> None:
        """Forget the shared crossover/rates and breaker state
        (tests; ops can call it after a hardware change)."""
        cls._min_device_bytes = 0.0
        cls._pinned_min_device_bytes = 0.0
        cls._dec_min_device_bytes = 0.0
        cls._delta_min_device_bytes = 0.0
        cls._probe_tick = 0
        cls._cpu_bps = {}
        cls._dev_bps = {}
        cls._warmed = set()
        cls._h2d_bps = 0.0
        cls._mesh_state = {}
        cls._mesh_key = None
        cls._last_device_ts = time.monotonic()
        cls._last_idle_probe_ts = time.monotonic()
        cls._prewarm_errors = []
        cls._dec_signatures = set()
        cls.reset_breaker()

    @classmethod
    def reset_breaker(cls) -> None:
        """Zero the breaker state/counters WITHOUT forgetting the
        learned crossover (bench runs isolate their breaker stats but
        keep the routing calibration)."""
        with cls._breaker_lock:
            cls._breaker_failures = 0
            cls._breaker_open = False
            cls._breaker_opens = 0
            cls._breaker_closes = 0

    @classmethod
    def _rekey_mesh(cls, key: Optional[Tuple]) -> None:
        """Swap the shared routing/link learner scalars to the state
        belonging to mesh shape ``key`` ((dp, sp), or None for single
        chip).  The h2d EWMA and the crossover thresholds model the
        AGGREGATE device+ICI bandwidth of the active mesh — carrying a
        single-chip estimate into a 4x2 mesh (or back) misroutes every
        batch until the learner recovers.  The outgoing shape's state
        is stashed, so flipping back restores what was learned."""
        if key == cls._mesh_key:
            return
        cls._mesh_state[cls._mesh_key] = {
            "h2d_bps": cls._h2d_bps,
            "min_device_bytes": cls._min_device_bytes,
            "pinned_min_device_bytes": cls._pinned_min_device_bytes,
            "dec_min_device_bytes": cls._dec_min_device_bytes,
            "delta_min_device_bytes": cls._delta_min_device_bytes,
            "dev_bps": dict(cls._dev_bps),
        }
        st = cls._mesh_state.get(key)
        if st is not None:
            cls._h2d_bps = st["h2d_bps"]
            cls._min_device_bytes = st["min_device_bytes"]
            cls._pinned_min_device_bytes = st["pinned_min_device_bytes"]
            cls._dec_min_device_bytes = st["dec_min_device_bytes"]
            cls._delta_min_device_bytes = st.get(
                "delta_min_device_bytes", 0.0)
            cls._dev_bps = dict(st["dev_bps"])
        # first time on this shape: keep the current scalars as the
        # seed (a mesh is at worst as fast as one of its chips)
        cls._mesh_key = key

    def _note_mesh(self, backend) -> None:
        """Fold the backend's active mesh into the batcher's
        telemetry: rekey the learner state to the mesh shape, set the
        mesh_* gauges, and (once) drain the backend's mesh_build
        events into the flight recorder so a misconfigured mesh is
        diagnosable from the admin socket."""
        info = None
        try:
            info = backend.mesh_info()
        except Exception:
            pass
        key = (info["dp"], info["sp"]) if info else None
        EncodeBatcher._rekey_mesh(key)
        dp = self.dperf
        if dp is not None and "mesh_dp" in dp._types:
            dp.set("mesh_dp", info["dp"] if info else 0)
            dp.set("mesh_sp", info["sp"] if info else 0)
            dp.set("mesh_devices", info["n_devices"] if info else 0)
        rec = self.recorder
        if rec is not None and not self._mesh_noted:
            self._mesh_noted = True
            for ev in list(getattr(backend, "mesh_events", ()) or ()):
                rec.note("mesh_build",
                         dp=ev.get("dp"), sp=ev.get("sp"),
                         n_devices=ev.get("n_devices"),
                         device_ids=ev.get("device_ids"))

    def _cpu_rate(self, lane: _Lane, key: Tuple, reqs: List) -> float:
        """CPU twin throughput of the lane for this geometry (input
        bytes/sec), measured once on the first request's real data;
        shared process-wide."""
        cls = EncodeBatcher
        rk, geom = lane.bucket(key), lane.geometry(key)
        rate = cls._cpu_bps.get(rk)
        if rate is None:
            one = reqs[:1]
            try:
                # build the twin (which builds and loads the native
                # library in a fresh checkout) and view the request
                # before the clock starts
                twin = self.cpu_twin(one[0].ec_impl, one[0].sinfo)
                stack = lane.form(key, one)
                t0 = time.monotonic()
                lane.probe(self, twin, key, one, stack)
                dt = max(time.monotonic() - t0, 1e-6)
                rate = _nbytes(stack) / dt
            except Exception:
                # no twin: a lane seeded from encode borrows encode's
                # measurement (same matmul cost model) rather than
                # guessing; encode itself has nothing to borrow
                if rk == geom:
                    raise
                rate = cls._cpu_bps.get(geom, 0.0)
            cls._cpu_bps[rk] = rate
        return rate

    def _learn_crossover(self, lane: _Lane, key: Tuple, reqs: List,
                         dev_time: float, in_bytes: int,
                         out_bytes: int,
                         trust_win: bool = True) -> None:
        """Compare the device's PIPELINED cost model against the CPU
        twin's predicted time for the same bytes and move the lane's
        routing threshold: lost -> raise it past this batch size; won
        big -> lower it.

        Two properties matter here (both were misrouting bugs):

        * the fenced ``dev_time`` is a SERIAL h2d + MXU + d2h sum,
          but in steady state consecutive batches overlap those legs
          (async dispatch, double-buffered staging) — so the cost the
          router should compare is ``max(h2d, compute, d2h)``, not
          the sum.  Judging the device on the serial number makes a
          device that wins pipelined look like it loses, and 100% of
          traffic lands on the twin.
        * a call that paid jit compile (or any one-off stall) must
          not teach the router: if this call ran far slower than the
          geometry's own steady-state EWMA predicts, it is an
          outlier, not a measurement."""
        try:
            cls = EncodeBatcher
            rk = lane.bucket(key)
            cpu_rate = max(self._cpu_rate(lane, key, reqs), 1.0)
            cpu_pred = in_bytes / cpu_rate
            # split the fenced window into transfer legs (measured
            # warm link rate) and the compute remainder
            h2d_s = d2h_s = 0.0
            if cls._h2d_bps > 0:
                h2d_s = min(dev_time, in_bytes / cls._h2d_bps)
                d2h_s = min(max(0.0, dev_time - h2d_s),
                            out_bytes / cls._h2d_bps)
            compute_s = max(0.0, dev_time - h2d_s - d2h_s)
            # compile/outlier rejection BEFORE the EWMA absorbs it:
            # against this geometry's steady-state compute rate, a
            # 5x-slower call is a one-off (jit compile, allocator
            # stall, scheduler hiccup), not the device's cost
            rate = cls._dev_bps.get(rk, 0.0)
            if rate > 0 and compute_s > 5.0 * (in_bytes / rate) \
                    and compute_s > 1e-3:
                return
            if compute_s > 0:
                bps = in_bytes / compute_s
                cls._dev_bps[rk] = bps if rate <= 0 else (
                    0.7 * rate + 0.3 * bps)
            # the PIPELINED device cost: legs overlap across batches,
            # so the sustained per-batch cost is the slowest leg
            dev_pipe = max(h2d_s, compute_s, d2h_s) \
                if (h2d_s or d2h_s) else dev_time
            cur = self._min_bytes(lane)
            if dev_pipe > cpu_pred:
                # the device LOST even with overlap credited: set the
                # crossover where the CPU would have taken as long as
                # this call's bottleneck leg (one losing measurement
                # teaches the whole region below it, not just 2x this
                # batch — bursts must not need a convergence loop)
                setattr(cls, lane.crossover, max(
                    cur, dev_pipe * cpu_rate / 2, self.crossover_min))
            elif trust_win and dev_pipe < cpu_pred / 2 and cur > 0:
                setattr(cls, lane.crossover, min(cur, in_bytes / 2))
        except Exception:
            pass                     # learning is best-effort

    def _bump(self, *attrs: str, n: int = 1) -> None:
        for a in attrs:
            setattr(self, a, getattr(self, a) + n)

    def _mark(self, reqs: List, event: Optional[str]) -> None:
        if event is not None:
            for r in reqs:
                if r.tracked is not None:
                    r.tracked.mark_event(event)

    def _dispatch(self, lane: _Lane, key: Tuple, reqs: List):
        """Issue the async device calls for one group: stack every
        request (lane.form) and launch tile by tile on the plug-in's
        async entry (signature-cached rows, StagingPool staging, full
        seven-phase ledger).  Returns (tiles, t_disp, in_bytes), or
        None on a dispatch failure (the join then falls back).
        On a multi-device host the backend's staged dispatch itself
        lays each group out with a NamedSharding(dp, None, sp) over
        the device mesh (jax_engine._staged_put + parallel/mesh.py
        kernels), so this production path rides every local chip —
        one dispatch is still ONE sharded GF matmul, and the ledger
        fans out per chip (AsyncBatch.ledgers)."""
        t_form = time.monotonic()
        waited = self._account_queue_wait(reqs, t_form)
        nstripes = sum(r.nstripes for r in reqs)
        with section("batcher.dispatch", lane=lane.name,
                     reqs=len(reqs), stripes=nstripes,
                     queue_wait_us=waited * 1e6) as sec:
            try:
                stack = lane.form(key, reqs)
                in_bytes = _nbytes(stack)
                if len(reqs) > 1:
                    self._note_copy(in_bytes, lane.concat_site)
            except Exception:
                # malformed request payload/geometry: NOT a device
                # fault (must not trip the breaker) — the fallback
                # fails the bad rider per-request and still serves
                # its group-mates
                return None
            # tile oversized batches at max_stripes: bounds per-call
            # device memory AND caps the largest compiled batch shape
            # at bucket(max_stripes) — the shape prewarm() compiles —
            # so a burst can never hit a never-seen (slow-compiling)
            # shape mid-benchmark.  All tiles dispatch before any
            # wait: h2d/MXU/d2h still overlap tile-to-tile.
            tile = max(1, self.max_stripes)
            tiles = err = None
            delay = self.device_retry_s
            for attempt in range(3):
                try:
                    faultlib.registry().hit(faultlib.DEVICE_DISPATCH)
                    tiles = [lane.launch(key, reqs, stack, i, i + tile)
                             for i in range(0, nstripes, tile)]
                    break
                except Exception as e:
                    # classified device dispatch failure: transient until
                    # proven otherwise — retry with capped backoff before
                    # charging the breaker
                    tiles, err = None, e
                    if attempt < 2 and delay > 0:
                        time.sleep(min(delay, 0.1))
                        delay *= 2
            if tiles is None:
                self._device_failure("dispatch", err)
                return None
            t_disp = time.monotonic()
            EncodeBatcher._last_device_ts = t_disp
            self.stage_seconds["batch_form"] += t_disp - t_form
            if self.bperf is not None:
                self.bperf.hinc("batch_stripes", nstripes)
                self.bperf.inc("h2d_bytes", in_bytes)
            self._mark(reqs, lane.dispatch_event)
            booked = lane.book(self, key, reqs, nstripes)
            if booked:
                sec.set_metadata(**booked)
            return (tiles, t_disp, in_bytes)

    def _join(self, lane: _Lane, key: Tuple, reqs: List, handle,
              trust_win: bool = True) -> None:
        """Join one in-flight device group (``handle`` from _dispatch;
        None when the dispatch failed): harvest the seven-phase
        ledgers, fold h2d samples into the link EWMA, teach the
        lane's crossover, and split the result back to each rider's
        callback.  Loss-direction learning runs on EVERY group
        (raising the threshold is safe even when sibling completions
        inflate dev_time — worst case small batches route to the CPU
        twin conservatively); the win direction (lowering it) only
        trusts single-group cycles (``trust_win``)."""
        result = None
        if handle is not None:
            tiles, t_disp, in_bytes = handle
            try:
                faultlib.registry().hit(faultlib.DEVICE_COMPLETION)
                result = _cat([t.wait() for t in tiles])
                dev_time = time.monotonic() - t_disp
                self._device_success()
                # fold any fenced WARM h2d samples the staging pool
                # took during this batch into the shared link EWMA —
                # real-traffic measurements keep the h2d/device/d2h
                # split and the overlap model honest
                for t in tiles:
                    hb = getattr(t, "h2d_bytes", 0)
                    hs = getattr(t, "h2d_seconds", 0.0)
                    if hb and hs > 0:
                        bps = hb / hs
                        EncodeBatcher._h2d_bps = bps \
                            if EncodeBatcher._h2d_bps <= 0 else (
                                0.7 * EncodeBatcher._h2d_bps
                                + 0.3 * bps)
            except Exception as e:
                # classified completion failure (a dispatched handle
                # cannot be re-waited, so no retry here — the CPU
                # serves the group and the breaker learns)
                result = None
                self._device_failure("completion", e)
        if result is None:
            if lane.fails_to_twin:
                self._twin(lane, key, reqs)
            else:
                self._singly(lane, key, reqs)
            return
        out_bytes = _nbytes(result)
        if self.adaptive_cpu:
            self._learn_crossover(lane, key, reqs, dev_time, in_bytes,
                                  out_bytes, trust_win=trust_win)
        c_calls, c_reqs, _c_twin, c_coalesced = lane.counters
        self._bump(c_calls)
        self._bump(c_reqs, n=len(reqs))
        if len(reqs) > 1:
            self._bump(c_coalesced, n=len(reqs))
        if self.perf is not None:
            self.perf.inc(f"ec_{lane.prefix}batch_calls")
            stripes = f"ec_{lane.prefix}batch_stripes"
            if stripes in self.perf._types:   # the OSD has encode's only
                self.perf.inc(stripes, sum(r.nstripes for r in reqs))
            if len(reqs) > 1:
                self.perf.inc(f"ec_{lane.prefix}batch_coalesced",
                              len(reqs))
        # split the fenced device window into transfer vs compute
        # using the link rate prewarm measured; without a measurement
        # the whole window is charged to "device"
        h2d_s = d2h_s = 0.0
        if self._h2d_bps > 0:
            h2d_s = min(dev_time, in_bytes / self._h2d_bps)
            d2h_s = min(dev_time - h2d_s, out_bytes / self._h2d_bps)
        self.stage_seconds["h2d"] += h2d_s
        self.stage_seconds["d2h"] += d2h_s
        self.stage_seconds["device"] += max(
            0.0, dev_time - h2d_s - d2h_s)
        if self.bperf is not None:
            self.bperf.hinc("dispatch_ms", dev_time * 1e3)
            self.bperf.inc("d2h_bytes", out_bytes)
            self.bperf.inc("device_reqs", len(reqs))
            if len(reqs) > 1:
                self.bperf.inc("coalesced_reqs", len(reqs))
        # harvest each tile's device-phase ledger (finalized by
        # AsyncBatch.wait above): feeds the phase accumulator, the
        # overlap engine, and the stall flight recorder.  A mesh
        # dispatch finalizes one clone per chip (.ledgers), so every
        # device gets its own waterfall/trace lane.
        for t in tiles:
            for led in (getattr(t, "ledgers", None) or
                        [getattr(t, "ledger", None)]):
                if led is not None:
                    led["group"] = lane.group
                self._observe_device_ledger(led)
        self._publish_device_telemetry(reqs[0].ec_impl)
        for r, out in zip(reqs, lane.split(self, key, reqs, result)):
            self._deliver(r, out)

    def _singly(self, lane: _Lane, key: Tuple, reqs: List,
                counts: Tuple[str, ...] = ()) -> None:
        """The per-request fallback behind a failed batched call:
        each rider on a REAL path of its own (for encode a jerasure
        twin of the same geometry — bit-exact by the corpus contract,
        and free of a broken device).  A request that still cannot be
        served gets cb(None), so its op fails with EIO instead of
        hanging."""
        for r in reqs:
            try:
                out = lane.single(self, key, r)
            except Exception:
                self._cb_error()
                out = None
            self._bump(*counts)
            self._deliver(r, out)

    def _twin(self, lane: _Lane, key: Tuple, reqs: List,
              impl=None) -> None:
        """Coalesced device-free completion: the whole group's
        stripes go through ONE batched call on the _BatchTwin (native
        C++ when available) — the coalescing win survives CPU
        routing — with the lane's per-request fallback behind it.
        The verdict was published at collect time; nothing re-routes
        here.  ``impl`` set means the plug-in's own synchronous
        batched call instead (_Lane.sync_call): a device call, with
        its fault site, its breaker charge and no twin counters."""
        t_form = time.monotonic()
        t_wall = time.time()
        books = lane.twin_books
        if books:
            self._account_queue_wait(reqs, t_form)
        self._mark(reqs, lane.twin_event)
        on_twin = impl is None
        nstripes = sum(r.nstripes for r in reqs)
        stack = outs = None
        try:
            if on_twin:
                try:
                    impl = self.cpu_twin(reqs[0].ec_impl,
                                         reqs[0].sinfo)
                except Exception:
                    if not lane.sync_call:
                        raise
                    impl, on_twin = reqs[0].ec_impl, False
            stack = lane.form(key, reqs)
            if books and len(reqs) > 1:
                self._note_copy(_nbytes(stack), lane.concat_site)
        except Exception:
            stack = None             # malformed input or no twin, not
                                     # a device fault: per-request
        if stack is not None:
            try:
                if not on_twin:
                    faultlib.registry().hit(faultlib.DEVICE_DISPATCH)
                result = lane.twin_call(impl, key, reqs, stack)
                # twin groups still fold into the device waterfall: a
                # coarse two-stamp ledger keyed device=-1 (host), so
                # dump_device and the bench attribution account for
                # every group regardless of routing.  No h2d/d2h
                # stamps — the whole interval charges to the compute
                # fence — and the overlap engine ignores negative
                # device ids (a host group has no transfer to hide
                # under compute).
                t_done = time.time()
                led = {"stage_acquire": t_wall, "compute_start": t_wall,
                       "compute_done": t_done, "deliver": t_done,
                       "group": lane.group}
                if on_twin:
                    led["device"] = -1
                else:
                    self._device_success()
                if books:
                    # twin work is pure compute: no transfer legs
                    self.stage_seconds["device"] += \
                        time.monotonic() - t_form
                    self.cpu_calls += 1
                    led.update(bytes=int(_nbytes(stack)),
                               stripes=int(nstripes))
                    if self.bperf is not None:
                        self.bperf.hinc("batch_stripes", nstripes)
                        self.bperf.inc("cpu_reqs", len(reqs))
                        if len(reqs) > 1:
                            self.bperf.inc("coalesced_reqs", len(reqs))
                self._observe_device_ledger(led)
                outs = list(lane.split(self, key, reqs, result))
            except Exception as e:
                outs = None
                if not on_twin:
                    self._device_failure(lane.group, e)
        if outs is None:
            self._singly(lane, key, reqs, lane.single_counts)
            return
        c_calls, c_reqs, c_twin, c_coalesced = lane.counters
        if lane.twin_is_call:
            self._bump(c_calls)
        self._bump(c_reqs, n=len(reqs))
        if on_twin:
            self._bump(c_twin, n=len(reqs))
        if len(reqs) > 1:
            self._bump(c_coalesced, n=len(reqs))
        if self.perf is not None:
            if "calls" in lane.twin_perf:
                self.perf.inc(f"ec_{lane.prefix}batch_calls")
            if "coalesced" in lane.twin_perf and len(reqs) > 1:
                self.perf.inc(f"ec_{lane.prefix}batch_coalesced",
                              len(reqs))
        for r, out in zip(reqs, outs):
            self._deliver(r, out)

    def _serve_sync(self, lane: _Lane, key: Tuple, reqs: List) -> None:
        """Completion-time path of a lane whose plug-in has a fenced
        synchronous batched call but no async entry (_Lane.sync_call:
        decode on a codec without decode_batch_async).  Routed here,
        by the crossover and the breaker alone (a counter, no
        recorder event); a device-bound group runs on its OWN thread
        — a slow synchronous call on the completion worker would
        stall every group queued behind it."""
        thr = self._min_bytes(lane)
        on_twin = (self.adaptive_cpu and thr > 0 and
                   lane.group_bytes(reqs) < thr) or \
            self._breaker_blocks()
        self._note_route(lane, key, reqs, on_twin, record=False)
        if on_twin:
            self._twin(lane, key, reqs)
            return
        t = threading.Thread(
            target=self._twin, args=(lane, key, reqs, reqs[0].ec_impl),
            name="ec-dec-dev", daemon=True)
        # tracked so stop() can honor its drain contract (no
        # continuation after the caller unmounts the store)
        self._dec_threads = [x for x in self._dec_threads
                             if x.is_alive()] + [t]
        t.start()

    # -- decode-side routing (consumed by ECBackend reads/recovery) ----
    def route_decode(self, nbytes: int) -> bool:
        """prefer_cpu() with the measurement the encode side has had
        since PR 5: one reason-coded ``dec_route_*`` verdict counter
        per call (device / learned / breaker_open) so perf dump and
        prometheus answer WHERE decode traffic actually ran.  True
        means the caller should take the CPU twin."""
        if EncodeBatcher._breaker_open:
            reason, to_cpu = "breaker_open", True
        elif (self.adaptive_cpu and self._min_bytes(_DEC) > 0
                and nbytes < self._min_bytes(_DEC)):
            reason, to_cpu = "learned", True
        else:
            reason, to_cpu = "device", False
        if self.dperf is not None and \
                f"dec_route_{reason}" in self.dperf._types:
            self.dperf.inc(f"dec_route_{reason}")
        return to_cpu

    def prefer_cpu(self, nbytes: int) -> bool:
        """Should a ``nbytes``-sized codec call avoid the device?
        Shares the encode path's learned crossover — the fixed
        dispatch/transfer cost is the same either direction."""
        if EncodeBatcher._breaker_open:
            return True              # breaker open: device is sick
        return (self.adaptive_cpu and self._min_device_bytes > 0
                and nbytes < self._min_device_bytes)

    def cpu_twin(self, ec_impl, sinfo: ecutil.StripeInfo):
        """The device-free BATCHED twin for this geometry (cached);
        bit-exact by the corpus contract, executing whole stripe
        batches in one native C++ kernel call (_BatchTwin).  Used by
        encode/decode fallback and by read/recovery decode when
        prefer_cpu() says the device round trip loses."""
        key = _geometry_key(ec_impl, sinfo)
        twin = self._cpu_twins.get(key)
        if twin is None:
            from ..ec import registry as ecreg
            prof = {"k": str(ec_impl.get_data_chunk_count()),
                    "m": str(ec_impl.get_coding_chunk_count()),
                    "technique": getattr(ec_impl, "technique",
                                         "reed_sol_van"),
                    "w": str(getattr(ec_impl, "w", 8))}
            ps = getattr(ec_impl, "packetsize", 0)
            if ps:
                prof["packetsize"] = str(ps)
            twin = _BatchTwin(ecreg.instance().factory("jerasure",
                                                       prof))
            self._cpu_twins[key] = twin
        return twin

    def _cpu_encode(self, req: _Req) -> Dict[int, bytes]:
        """Device-free encode through the CPU twin; jerasure lacks the
        batched device API, so ecutil.encode takes its per-stripe CPU
        loop."""
        twin = self.cpu_twin(req.ec_impl, req.sinfo)
        return ecutil.encode(req.sinfo, twin, req.data)

    def _publish_device_telemetry(self, ec_impl) -> None:
        """Refresh the ec_device staging/link gauges from the codec's
        StagingPool after a device completion (completion worker
        only).  A stall-grow since the last look is an incident-grade
        event: it means the ring wedged past STALL_S and the pool
        grew to protect the write path — flight-record it."""
        dp = self.dperf
        rec = self.recorder
        backend = getattr(getattr(ec_impl, "core", None),
                          "backend", None)
        if backend is not None and hasattr(backend, "memory_stats"):
            # remembered so dump_device can report memory accounting
            # even on a daemon with no perf plumbing (unit stubs)
            self._last_backend = backend
        if backend is not None and hasattr(backend, "mesh_info"):
            # keep the mesh gauges / learner keying current even when
            # prewarm was skipped (ec_tpu_prewarm=false paths)
            self._note_mesh(backend)
        if dp is None and rec is None:
            return
        pool = getattr(backend, "staging", None)
        if pool is not None:
            try:
                st = pool.stats()
            except Exception:
                st = None
            if st:
                if dp is not None:
                    dp.set("staging_hits", st["hits"])
                    dp.set("staging_allocs", st["allocs"])
                    dp.set("staging_stall_allocs",
                           st["stall_allocs"])
                    dp.set("staging_slots", st["slots"])
                    dp.set("staging_in_flight", st["in_flight"])
                if st["stall_allocs"] > self._staging_stalls_seen:
                    self._staging_stalls_seen = st["stall_allocs"]
                    if rec is not None:
                        rec.note("staging", event="stall_grow",
                                 stall_allocs=st["stall_allocs"],
                                 slots=st["slots"])
        if dp is not None:
            dp.set("h2d_bps", int(EncodeBatcher._h2d_bps))
            if self._last_backend is not None and \
                    "staging_host_bytes_now" in dp._types:
                try:
                    mem = self._last_backend.memory_stats()
                except Exception:
                    mem = None
                if mem:
                    dp.set("staging_host_bytes_now",
                           mem["staging_host_bytes"])
                    dp.set("staging_host_bytes_peak",
                           mem["staging_host_bytes_peak"])
                    dp.set("dev_matrix_bytes_now",
                           mem["dev_matrix_bytes"])
                    dp.set("compile_cache_entries",
                           mem["compile_cache_entries"])

    def _observe_device_ledger(self, led) -> None:
        """Fold one completed group's device-phase ledger into the
        accumulator; stall-check the h2d and compute-fence phases
        (the two that bound the pipeline), mirroring lock_stall.
        Completion-worker only.  Must not raise."""
        if not led:
            return
        try:
            self.ledger_accum.observe(led)
        except Exception:
            return
        self._ledger_completions += 1
        dp = self.dperf
        if dp is not None and self._ledger_completions % 32 == 0 and \
                "pipeline_overlap_frac" in dp._types:
            # periodic refresh: sorting the 256-deep recent ring on
            # every completion is not free, 1-in-32 is
            try:
                ov = overlap_stats(self.ledger_accum.recent())
                dp.set("pipeline_overlap_frac",
                       ov["pipeline_overlap_frac"])
            except Exception:
                pass
        stall = self.phase_stall_s
        if stall <= 0:
            return
        for phase, a, b in (("h2d", "h2d_start", "h2d_done"),
                            ("fence", "compute_start",
                             "compute_done")):
            ta, tb = led.get(a), led.get(b)
            if ta is None or tb is None or tb - ta < stall:
                continue
            if dp is not None and "device_phase_stalls" in dp._types:
                dp.inc("device_phase_stalls")
            rec = self.recorder
            if rec is not None:
                rec.note("device_stall", phase=phase,
                         ms=round((tb - ta) * 1e3, 3),
                         device=led.get("device", 0),
                         bytes=led.get("bytes", 0))
                rec.auto_dump("device-phase-stall")

    def device_dump(self) -> dict:
        """``dump_device`` admin-command payload: the per-phase
        waterfall (with p50/p99 + overlap verdict), memory
        accounting, and the batcher's coarse stage split."""
        dump = self.ledger_accum.dump()
        mem = None
        backend = self._last_backend
        if backend is not None:
            try:
                mem = backend.memory_stats()
            except Exception:
                mem = None
        mesh = None
        if backend is not None and hasattr(backend, "mesh_info"):
            try:
                mesh = backend.mesh_info()
            except Exception:
                mesh = None
        cls = EncodeBatcher
        return {
            "ledger": dump,
            "overlap": dump.get("overlap"),
            "memory": mem,
            "mesh": mesh,
            "stage_seconds": dict(self.stage_seconds),
            "breaker_open": bool(cls._breaker_open),
            # where each lane's requests ran: the perf counters fold
            # the three lanes together (ec_batcher.device_reqs)
            "lanes": {
                "encode": {"reqs": self.reqs_total,
                           "twin_reqs": self.cpu_reqs},
                "decode": {"reqs": self.dec_reqs,
                           "twin_reqs": self.dec_cpu_reqs,
                           "signatures": self.dec_signatures,
                           "rows_out": self.dec_rows_out,
                           "rows_wanted": self.dec_rows_wanted},
                "delta": {"reqs": self.delta_reqs,
                          "twin_reqs": self.delta_cpu_reqs},
            },
            "kernels": dict(getattr(backend, "kernel_calls", None)
                            or {}),
            # row sets bound to a program, and the executables those
            # bindings needed (JaxBackend; process-wide)
            "row_sets_bound": getattr(backend, "row_sets_bound", 0),
            "row_programs_built": getattr(backend,
                                          "row_programs_built", 0),
            # the process's caches of solved recovery rows
            # (ops/engine.py RecoveryRowsCache): hits, misses, entries
            **rows_cache_stats(),
            "device_errors": self.device_errors,
            "last_device_error": self.last_device_error,
            "prewarm_errors": list(cls._prewarm_errors),
            # what prewarm and the learner measured for the router
            # (process-wide, like the device they describe)
            "router": {
                "h2d_bps": cls._h2d_bps,
                "cpu_bps": {repr(k): v
                            for k, v in cls._cpu_bps.items()},
                "dev_bps": {repr(k): v
                            for k, v in cls._dev_bps.items()},
                "min_device_bytes": cls._min_device_bytes,
                "dec_min_device_bytes": cls._dec_min_device_bytes,
                "delta_min_device_bytes":
                    cls._delta_min_device_bytes,
            },
        }

    def device_trace_block(self) -> dict:
        """Raw recent group ledgers (+ memory snapshot) for the
        unified trace exporter's per-device phase lanes."""
        mem = None
        backend = self._last_backend
        if backend is not None:
            try:
                mem = backend.memory_stats()
            except Exception:
                mem = None
        return {"ledgers": self.ledger_accum.recent(), "memory": mem}

    def _account_queue_wait(self, reqs: List[_Req],
                            now: float) -> float:
        """-> seconds the group's requests waited, summed."""
        total = 0.0
        for r in reqs:
            w = max(0.0, now - r.t_enq)
            total += w
            self.stage_seconds["queue_wait"] += w
            if self.bperf is not None:
                self.bperf.hinc("queue_wait_us", w * 1e6)
        return total

