"""The yardstick's own arithmetic, runnable by hand on a CPU:

    python3 -m pytest benchmark/tests/test_reduction.py -q

The trace reduction (busy/idle union, module-name attribution, gap
attribution) on the small recorded trace kept beside this file, the
table of peaks, the work functions and the plain reference's matrix.
"""
import itertools
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from harness import spec, trace, work  # noqa: E402

reference = spec.reference("reed_sol_van_w8")

RECORDED = os.path.join(HERE, "recorded_trace.json.gz")


def test_union_and_gaps():
    iv = [(0, 10), (5, 20), (30, 40), (35, 36)]
    assert trace.union_seconds(iv) == pytest.approx(30e-9)
    assert trace.gaps_of(iv, 0, 50) == [(20, 30), (40, 50)]
    assert trace.gaps_of([], 0, 5) == [(0, 5)]


def test_gap_goes_to_shortest_covering_span_or_is_unspanned():
    host = [(0, 100, "client.wait", "t1"), (10, 30, "shard_args", "t2"),
            (12, 20, "PjitFunction(fn)", "t2")]
    gaps = [(14, 18), (40, 60), (150, 160)]
    got = dict(trace.attribute_gaps(gaps, host))
    assert got == {"PjitFunction(fn)": pytest.approx(4e-9),
                   trace.UNSPANNED: pytest.approx(30e-9)}


def synthetic():
    dev = {"XLA Modules": [("jit_gf8_mxu_pallas(1)", 100.0, 50.0),
                           ("jit__apply_byte_domain(2)", 300.0, 40.0),
                           ("jit_other(3)", 500.0, 10.0)],
           "XLA Ops": [("fusion.1", 100.0, 20.0), ("custom-call.2", 120.0, 30.0),
                       ("fusion.7", 300.0, 40.0), ("copy.3", 500.0, 10.0)]}
    host = {"python": [(trace.WINDOW_SPAN, 0.0, 1000.0),
                       ("client.wait", 0.0, 1000.0),
                       ("np.asarray(jax.Array)", 160.0, 100.0)]}
    return {"/device:TPU:0": dev, "/host:CPU": host}


def test_reduce_attributes_ops_to_families_by_module_name():
    red = trace.reduce(synthetic(), spec.kernel_families())
    assert red["window_s"] == pytest.approx(1000e-9)
    assert red["busy_s"] == pytest.approx(100e-9)
    assert red["family_seconds"]["gf8_mxu_pallas"] == pytest.approx(50e-9)
    assert red["family_seconds"]["bitplane_xla_crc"] == pytest.approx(40e-9)
    assert red["unmatched_module_seconds"] == {
        "jit_other(3)": pytest.approx(10e-9)}
    gaps = dict(red["idle_gaps"])
    assert gaps["np.asarray(jax.Array)"] == pytest.approx(150e-9)
    assert sum(gaps.values()) == pytest.approx(900e-9)
    assert red["device_ops"][0] == ["fusion.7", pytest.approx(40e-9)]


def test_no_device_plane_or_no_device_op_is_an_error_not_a_zero():
    planes = synthetic()
    with pytest.raises(RuntimeError, match="not a chip run"):
        trace.reduce({"/host:CPU": planes["/host:CPU"]}, [])
    planes["/device:TPU:0"]["XLA Ops"] = []
    with pytest.raises(RuntimeError, match="no operation ran"):
        trace.reduce(planes, spec.kernel_families())


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded trace beside this file")
def test_recorded_chip_trace_reduces_to_what_was_read_by_hand():
    """1.5 s of k8m4.write_4m on a TPU v5 lite (my chip run, PR 24)."""
    planes = trace.load_recorded(RECORDED)
    # recorded before the Pallas kernel's module had a name of its own
    # (PR 25): its family file went with PR 29, the pattern lives here
    as_recorded = spec.kernel_families() + [{
        "family": "gf_mxu_pallas", "gf_work": True,
        "module_patterns": ["^jit_fn(\\(|$)"]}]
    red = trace.reduce(planes, as_recorded)
    assert red["window_s"] == pytest.approx(1.5)
    # busy is the union, never more than the sum of the ops' times
    ops = planes["/device:TPU:0"][trace.OPS_LINE]
    total = sum(d for _, _, d in ops) / 1e9
    assert 0 < red["busy_s"] <= total * (1 + 1e-9)
    assert red["busy_s"] < red["window_s"]
    # every device op of this window ran inside a module that a
    # kernels/*.json file names
    assert red["unmatched_module_seconds"] == {}
    assert red["n_device_ops"] == 581
    assert red["busy_s"] == pytest.approx(0.005987605, rel=1e-6)
    assert red["family_seconds"]["gf_mxu_pallas"] == pytest.approx(
        0.000367732, rel=1e-5)
    assert red["family_seconds"]["bitplane_xla_crc"] == pytest.approx(
        0.005619873, rel=1e-5)
    assert red["family_modules"]["gf_mxu_pallas"] == [
        "jit_fn(1337989364977886815)"]
    assert sum(red["family_seconds"].values()) == pytest.approx(total)
    idle = sum(s for _, s in red["idle_gaps"])
    assert idle <= red["window_s"] - red["busy_s"] + 1e-9


def test_unknown_device_is_an_error():
    assert spec.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no default"):
        spec.peaks("TPU v9 imaginary")


def test_work_by_shape():
    enc = work.lane_request_work("encode", 8, 4, 4 << 20, 4096)
    assert enc == {"bytes_in": 4 << 20, "bytes_out": 2 << 20,
                   "int8_ops": (2 << 20) * 8 * 128}
    dec = work.lane_request_work("decode", 4, 2, 4 << 20, 4096)
    assert dec["bytes_out"] == 1 << 20 and dec["int8_ops"] == (1 << 20) * 512
    dlt = work.lane_request_work("delta", 4, 2, 4096, 4096)
    assert dlt == {"bytes_in": 4096, "bytes_out": 8192,
                   "int8_ops": 8192 * 128}
    lanes = {"encode": {"reqs": 10, "twin_reqs": 0},
             "decode": {"reqs": 0, "twin_reqs": 0},
             "delta": {"reqs": 3, "twin_reqs": 3}}
    ops = [{"op": "write_full", "io_bytes": 4 << 20}]
    total = work.window_work(lanes, ops, 8, 4, 4096)
    assert total["requests"] == 10 and total["bytes"] == 10 * (6 << 20)
    least = work.least_seconds(total, spec.peaks("TPU v5 lite"))
    assert least["binds"] == "hbm_bandwidth"
    assert least["seconds"] == pytest.approx(10 * (6 << 20) / 819e9)


def _gf_inv_matrix(a):
    """Gauss-Jordan over GF(2^8); raises on a singular matrix."""
    n = len(a)
    a = [list(r) + [1 if i == j else 0 for j in range(n)]
         for i, r in enumerate(a)]
    for c in range(n):
        p = next(r for r in range(c, n) if a[r][c])
        a[c], a[p] = a[p], a[c]
        inv = reference.gf_div(1, a[c][c])
        a[c] = [reference.gf_mul(inv, v) for v in a[c]]
        for r in range(n):
            if r != c and a[r][c]:
                f = a[r][c]
                a[r] = [v ^ reference.gf_mul(f, w)
                        for v, w in zip(a[r], a[c])]
    return [r[n:] for r in a]


@pytest.mark.parametrize("k,m", [(4, 2), (8, 4)])
def test_reference_code_is_systematic_and_mds(k, m):
    """Any k of the k+m shards determine the object: every k x k
    submatrix of [I; M] is invertible."""
    M = reference.vandermonde_coding_matrix(k, m).tolist()
    assert M[0] == [1] * k and all(r[0] == 1 for r in M)
    full = [[1 if i == j else 0 for j in range(k)] for i in range(k)] + M
    for rows in itertools.combinations(range(k + m), k):
        _gf_inv_matrix([full[r] for r in rows])     # StopIteration if not


def test_reference_shards_decode_back():
    k, m, su = 4, 2, 64
    rng = np.random.default_rng(7)
    obj = rng.bytes(k * su * 3)
    shards = reference.shards_of(obj, {"technique": "reed_sol_van", "k": k,
                                       "m": m, "w": 8}, su)
    assert shards == reference.stripe_shards(obj, k, m, su)
    assert b"".join(
        b"".join(shards[i][s * su:(s + 1) * su] for i in range(k))
        for s in range(3)) == obj
    # lose data shards 1 and 3, solve them back from the rest
    M = reference.vandermonde_coding_matrix(k, m).tolist()
    full = [[1 if i == j else 0 for j in range(k)] for i in range(k)] + M
    keep = [0, 2, 4, 5]
    inv = _gf_inv_matrix([full[r] for r in keep])
    got = []
    for i in range(k):
        acc = np.zeros(len(shards[0]), dtype=np.uint8)
        for c, r in zip(inv[i], keep):
            if c:
                acc ^= reference.MUL[c][np.frombuffer(shards[r], np.uint8)]
        got.append(acc.tobytes())
    assert got == shards[:k]
