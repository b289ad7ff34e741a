"""The plain reference of ``technique=cauchy_good w=8``: what a k+m
pool on jerasure's "good" Cauchy Reed-Solomon code must hold.

Straight numpy, written from the public description (Blömer, Kalfane,
Karp, Karpinski, Luby, Zuckerman, "An XOR-Based Erasure-Resilient
Coding Scheme", 1995; Plank & Xu, "Optimizing Cauchy Reed-Solomon
Codes for Fault-Tolerant Network Storage Applications", 2006; the
jerasure manual, "Cauchy Reed-Solomon Coding"):

* GF(2^8) over x^8+x^4+x^3+x^2+1 (0x11D).
* The m x k Cauchy matrix ``C[i][j] = 1 / (i XOR (m + j))``.
* Improved as ``cauchy_good`` does for m != 2 (jerasure's
  ``cauchy_improve_coding_matrix``): every column divided by its
  first-row element, so that the first row is all ones; then each
  later row divided by the one of its elements that leaves strictly
  fewer ones in the row's bit-matrix than any choice before it, the
  row as it stands included, or left alone where none does.
* Expanded to the ``m*w x k*w`` bit-matrix: the w x w block of an
  element e has in column x the bits of ``e * 2^x``, bit l in row l.
* Applied in **packet layout**: a chunk is regions of w packets of
  ``packetsize`` bytes, and coding packet (i, r) of a region is the XOR
  of the data packets (j, x) of that region whose bit is set in row
  ``i*w + r``, column ``j*w + x``.

Imports nothing of ceph_tpu and takes nothing it made: no matrix, no
table, no schedule.  ``stripe_unit`` bytes of shard i in stripe s are
object bytes ``[s*k*su + i*su, s*k*su + (i+1)*su)``.

What cannot be checked here: the jerasure submodule is empty in the
reference checkout, so agreement is with the description above and
with this repo's CPU port of it (``benchmark/tests``), not with the
library's own output.  For m = 2 jerasure takes precomputed matrices
(its ``cbest`` tables), which are not reproduced: this module refuses
m = 2, and no configuration uses it there.
"""
import functools

import numpy as np

W = 8
PRIM_POLY = 0x11D


def gf_mul(a: int, b: int) -> int:
    """Shift and add, reduced by the polynomial: no table."""
    out = 0
    while b:
        if b & 1:
            out ^= a
        a <<= 1
        if a & 0x100:
            a ^= PRIM_POLY
        b >>= 1
    return out


def gf_inv(a: int) -> int:
    if a == 0:
        raise ZeroDivisionError("GF(2^8) inverse of zero")
    return next(b for b in range(1, 256) if gf_mul(a, b) == 1)


def n_ones(e: int) -> int:
    """Ones in the w x w bit-matrix of the element e."""
    total = 0
    for _ in range(W):
        total += bin(e).count("1")
        e = gf_mul(e, 2)
    return total


def cauchy_matrix(k: int, m: int) -> list:
    if k + m > 256:
        raise ValueError("k + m must be at most 2^w")
    return [[gf_inv(i ^ (m + j)) for j in range(k)] for i in range(m)]


def improve(matrix: list) -> list:
    """jerasure's ``cauchy_improve_coding_matrix``, as described."""
    rows = [list(r) for r in matrix]
    k = len(rows[0])
    for j in range(k):
        if rows[0][j] != 1:
            inv = gf_inv(rows[0][j])
            for r in rows:
                r[j] = gf_mul(r[j], inv)
    for row in rows[1:]:
        best = sum(n_ones(e) for e in row)
        best_j = None
        for j in range(k):
            if row[j] == 1:
                continue
            inv = gf_inv(row[j])
            ones = sum(n_ones(gf_mul(e, inv)) for e in row)
            if ones < best:
                best, best_j = ones, j
        if best_j is not None:
            inv = gf_inv(row[best_j])
            row[:] = [gf_mul(e, inv) for e in row]
    return rows


def coding_matrix(k: int, m: int) -> list:
    """The m x k ``cauchy_good`` coding matrix over GF(2^8)."""
    if m == 2:
        raise ValueError("cauchy_good at m=2 is jerasure's precomputed "
                         "cbest matrix, which this reference does not "
                         "reproduce")
    return improve(cauchy_matrix(k, m))


def bitmatrix_of(matrix: list) -> np.ndarray:
    """[m, k] over GF(2^8) -> [m*w, k*w] over GF(2)."""
    m, k = len(matrix), len(matrix[0])
    bits = np.zeros((m * W, k * W), dtype=np.uint8)
    for i in range(m):
        for j in range(k):
            e = matrix[i][j]
            for x in range(W):
                for l in range(W):
                    bits[i * W + l, j * W + x] = (e >> l) & 1
                e = gf_mul(e, 2)
    return bits


@functools.lru_cache(maxsize=8)
def coding_bitmatrix(k: int, m: int) -> np.ndarray:
    return bitmatrix_of(coding_matrix(k, m))


def stripe_shards(obj: bytes, k: int, m: int, stripe_unit: int,
                  packetsize: int) -> list:
    """All k+m shard byte strings of one object whose length is a
    whole number of stripes."""
    width = k * stripe_unit
    if len(obj) % width:
        raise ValueError(f"object of {len(obj)} bytes is not a whole "
                         f"number of {width}-byte stripes")
    if stripe_unit % (W * packetsize):
        raise ValueError(
            f"a chunk of {stripe_unit} bytes is not a whole number of "
            f"regions of w * packetsize = {W * packetsize} bytes")
    bits = coding_bitmatrix(k, m)
    a = np.frombuffer(obj, dtype=np.uint8).reshape(-1, k, stripe_unit)
    data = [np.ascontiguousarray(a[:, j]).reshape(-1) for j in range(k)]
    out = [d.tobytes() for d in data]
    # packets[j][:, x, :] is packet x of every region of data chunk j
    packets = [d.reshape(-1, W, packetsize) for d in data]
    for i in range(m):
        coded = np.zeros_like(packets[0])
        for r in range(W):
            acc = coded[:, r, :]
            for c in np.flatnonzero(bits[i * W + r]):
                acc ^= packets[c // W][:, c % W, :]
        out.append(coded.tobytes())
    return out


def shards_of(obj: bytes, profile: dict, stripe_unit: int) -> list:
    """The entry point every module of ``references/`` has."""
    if profile.get("technique") != "cauchy_good" or \
            int(profile.get("w", W)) != W:
        raise ValueError(f"cauchy_good_w8 does not serve the profile "
                         f"{profile}")
    return stripe_shards(obj, int(profile["k"]), int(profile["m"]),
                         stripe_unit, int(profile.get("packetsize", 2048)))
