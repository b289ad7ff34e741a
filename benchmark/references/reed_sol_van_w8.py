"""The plain reference of ``technique=reed_sol_van w=8``: what a k+m
pool on that code must hold.  (``harness/reference.py`` until PR 29.)

Straight numpy, written from the public description (Plank, "A
Tutorial on Reed-Solomon Coding for Fault-Tolerance in RAID-like
Systems", 1997, with Plank & Ding's 2005 correction): GF(2^8) over
x^8+x^4+x^3+x^2+1, the (k+m) x k Vandermonde matrix V[i][j] = i^j
reduced to systematic form by column operations, its coding part
scaled so that its first row and first column are ones, and its last
m rows applied to the k data chunks of every stripe.  (jerasure's own
library starts from an "extended" matrix whose last row is 0..0 1; see
PERF.md, Open questions.)

Imports nothing of ceph_tpu and takes nothing it made: no matrix, no
table.  ``stripe_unit`` bytes of shard i in stripe s are object bytes
``[s*k*su + i*su, s*k*su + (i+1)*su)``.
"""
import functools

import numpy as np

PRIM_POLY = 0x11D


def _tables():
    exp = np.zeros(512, dtype=np.int64)
    log = np.zeros(256, dtype=np.int64)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= PRIM_POLY
    exp[255:510] = exp[0:255]
    return exp, log


_EXP, _LOG = _tables()


def gf_mul(a: int, b: int) -> int:
    if a == 0 or b == 0:
        return 0
    return int(_EXP[_LOG[a] + _LOG[b]])


def gf_div(a: int, b: int) -> int:
    if b == 0:
        raise ZeroDivisionError("GF(2^8) division by zero")
    if a == 0:
        return 0
    return int(_EXP[(_LOG[a] - _LOG[b]) % 255])


def _mul_table() -> np.ndarray:
    t = np.zeros((256, 256), dtype=np.uint8)
    for a in range(1, 256):
        la = _LOG[a]
        t[a, 1:] = _EXP[la + _LOG[1:256]]
    return t


MUL = _mul_table()


@functools.lru_cache(maxsize=8)
def vandermonde_coding_matrix(k: int, m: int) -> np.ndarray:
    """The systematic Vandermonde code's coding rows -> [m, k]."""
    rows, cols = k + m, k
    d = [[0] * cols for _ in range(rows)]
    for i in range(rows):
        v = 1
        for j in range(cols):
            d[i][j] = v
            v = gf_mul(v, i)
    for i in range(1, cols):
        # a row at or below i with a non-zero in column i, swapped up
        j = i
        while j < rows and d[j][i] == 0:
            j += 1
        if j >= rows:
            raise ValueError("vandermonde matrix is singular")
        if j != i:
            d[i], d[j] = d[j], d[i]
        if d[i][i] != 1:
            inv = gf_div(1, d[i][i])
            for r in range(rows):
                d[r][i] = gf_mul(inv, d[r][i])
        for j in range(cols):
            e = d[i][j]
            if j != i and e != 0:
                for r in range(rows):
                    d[r][j] ^= gf_mul(e, d[r][i])
    # first coding row all ones, then each later row's first column one
    for j in range(cols):
        e = d[cols][j]
        if e != 1:
            inv = gf_div(1, e)
            for r in range(cols, rows):
                d[r][j] = gf_mul(inv, d[r][j])
    for r in range(cols + 1, rows):
        e = d[r][0]
        if e != 1:
            inv = gf_div(1, e)
            for j in range(cols):
                d[r][j] = gf_mul(d[r][j], inv)
    for i in range(cols):
        if [d[i][j] for j in range(cols)] != \
                [1 if j == i else 0 for j in range(cols)]:
            raise ValueError("distribution matrix is not systematic")
    return np.array(d[cols:], dtype=np.uint8)


def stripe_shards(obj: bytes, k: int, m: int, stripe_unit: int,
                  matrix: np.ndarray = None) -> list:
    """All k+m shard byte strings of one object whose length is a
    whole number of stripes."""
    width = k * stripe_unit
    if len(obj) % width:
        raise ValueError(f"object of {len(obj)} bytes is not a whole "
                         f"number of {width}-byte stripes")
    if matrix is None:
        matrix = vandermonde_coding_matrix(k, m)
    a = np.frombuffer(obj, dtype=np.uint8).reshape(-1, k, stripe_unit)
    data = [np.ascontiguousarray(a[:, i]).reshape(-1) for i in range(k)]
    out = [d.tobytes() for d in data]
    for j in range(m):
        acc = np.zeros(data[0].shape, dtype=np.uint8)
        for i in range(k):
            c = int(matrix[j, i])
            if c == 1:
                acc ^= data[i]
            elif c:
                acc ^= MUL[c][data[i]]
        out.append(acc.tobytes())
    return out


def shards_of(obj: bytes, profile: dict, stripe_unit: int) -> list:
    """The entry point every module of ``references/`` has: the k+m
    shard byte strings of an object that is a whole number of stripes,
    for the pool profile of a configuration file."""
    if profile.get("technique") != "reed_sol_van" or \
            int(profile.get("w", 8)) != 8:
        raise ValueError(f"reed_sol_van_w8 does not serve the profile "
                         f"{profile}")
    return stripe_shards(obj, int(profile["k"]), int(profile["m"]),
                         stripe_unit)
