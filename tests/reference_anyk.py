"""A plain any-k decoder for ``technique=reed_sol_van w=8``: the object
back from ANY k of the k+m shards that
``benchmark/references/reed_sol_van_w8.py::shards_of`` gives.

Straight numpy in that file's style and independent of ``ceph_tpu``:
GF(2^8) log tables from x^8+x^4+x^3+x^2+1 (0x11d) built here, a
Gauss-Jordan inverse over that field written here, nothing imported
from the package.  The code's generator matrix is not rebuilt either:
it is read off the plain encoder itself, by encoding the k objects
that hold a single 1 (shard r of unit object j is G[r][j], since the
code is linear over GF(2^8) byte by byte).  So this decodes whatever
linear w=8 code the encoder is, and shares with the system under test
neither a matrix, a table nor an inverse.

What a ``fast_read`` pool answers from is exactly this: whichever k
shards came first, data and parity mixed.
"""
import functools
import importlib.util
import os

import numpy as np

PRIM_POLY = 0x11D
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


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


def gf_inv(a: int) -> int:
    if a == 0:
        raise ZeroDivisionError("GF(2^8): zero has no inverse")
    return int(_EXP[(255 - _LOG[a]) % 255])


def mul_bytes(c: int, data: np.ndarray) -> np.ndarray:
    """c * data over GF(2^8), byte by byte."""
    if c == 0:
        return np.zeros_like(data)
    if c == 1:
        return data.copy()
    out = _EXP[_LOG[c] + _LOG[data]].astype(np.uint8)
    out[data == 0] = 0
    return out


@functools.lru_cache(maxsize=None)
def encoder():
    """The plain encoder, loaded by path (``benchmark`` is no package)."""
    path = os.path.join(ROOT, "benchmark", "references",
                        "reed_sol_van_w8.py")
    spec = importlib.util.spec_from_file_location("plain_rsv_w8", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def shards_of(obj: bytes, k: int, m: int, stripe_unit: int) -> list:
    """The k+m shards of an object that is a whole number of stripes."""
    return encoder().shards_of(
        obj, {"technique": "reed_sol_van", "k": k, "m": m, "w": 8},
        stripe_unit)


@functools.lru_cache(maxsize=8)
def generator(k: int, m: int) -> tuple:
    """G, (k+m) rows of k: shard r = sum_j G[r][j] * data chunk j."""
    cols = [shards_of(bytes(1 if i == j else 0 for i in range(k)),
                      k, m, 1) for j in range(k)]
    return tuple(tuple(cols[j][r][0] for j in range(k))
                 for r in range(k + m))


def invert(rows) -> list:
    """Gauss-Jordan inverse of a square matrix over GF(2^8)."""
    n = len(rows)
    a = [list(r) + [1 if i == j else 0 for j in range(n)]
         for i, r in enumerate(rows)]
    for col in range(n):
        piv = next((r for r in range(col, n) if a[r][col]), None)
        if piv is None:
            raise ValueError("these shards do not determine the object")
        a[col], a[piv] = a[piv], a[col]
        inv = gf_inv(a[col][col])
        a[col] = [gf_mul(inv, v) for v in a[col]]
        for r in range(n):
            f = a[r][col]
            if r != col and f:
                a[r] = [v ^ gf_mul(f, p) for v, p in zip(a[r], a[col])]
    return [row[n:] for row in a]


def decode(shards: dict, k: int, m: int, stripe_unit: int = 4096) -> bytes:
    """The object (padded to whole stripes, as it is stored) from the
    k lowest-numbered of ``shards`` ({shard id: bytes}; more than k
    may be given, fewer raises)."""
    ids = sorted(shards)[:k]
    if len(ids) < k or ids[-1] >= k + m or ids[0] < 0:
        raise ValueError(f"need {k} shards of 0..{k + m - 1}, "
                         f"have {sorted(shards)}")
    g = generator(k, m)
    inv = invert([g[i] for i in ids])
    have = [np.frombuffer(bytes(shards[i]), dtype=np.uint8) for i in ids]
    data = []
    for j in range(k):
        acc = np.zeros(have[0].shape, dtype=np.uint8)
        for c, chunk in zip(inv[j], have):
            if c:
                acc ^= mul_bytes(c, chunk)
        data.append(acc.reshape(-1, stripe_unit))
    # stripe s of the object is chunk 0's unit s, chunk 1's unit s, ...
    return np.stack(data, axis=1).tobytes()
