"""Decode rows as an operand of one program per shape (ISSUE 34).

On a TPU a GF row set (a pool's coding matrix, one erasure signature's
recovery rows) is bound to its kernel family's one jitted program
(``jax_engine.rows_program``, ``BoundRows``) and no longer compiled
into a program of its own.  A CPU cannot run the Mosaic kernels, so
these tests push the same entries through them in Pallas interpret
mode: the kernel choosers are steered from here, the program gains no
option for it.  Held to the benchmark's plain references (numpy alone,
nothing of ceph_tpu), signature by signature.
"""
import importlib.util
import itertools
import os

import numpy as np
import pytest

import jax.monitoring

from ceph_tpu.ec import registry as ecreg
from ceph_tpu.ops import jax_engine as je
from ceph_tpu.ops.jax_engine import BoundRows, JaxBackend

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CAUCHY = {"technique": "cauchy_good", "k": "10", "m": "4", "w": "8",
          "packetsize": "2048"}
CAUCHY_UNIT = 8 * 2048                   # one region a chunk
RS = {"technique": "reed_sol_van", "k": "4", "m": "2", "w": "8"}
RS_UNIT = 4096

LOWERED = [0]
PACKET_KERNEL = je.packet_kernel         # the platform's own choice
ROWS_PROGRAM = je.rows_program


def _on_event(event: str, duration: float, **kw) -> None:
    """As benchmark/run.py's CompileCount counts a lowered program."""
    if event.endswith("jaxpr_to_mlir_module_duration"):
        LOWERED[0] += 1


jax.monitoring.register_event_duration_secs_listener(_on_event)


def reference(name: str):
    path = os.path.join(ROOT, "benchmark", "references", name + ".py")
    spec = importlib.util.spec_from_file_location("ref_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Served:
    """A tpu-plugin codec on a backend of its own (one device), and
    what the plain reference stores of ``stripes`` seeded stripes."""

    def __init__(self, profile: dict, unit: int, ref: str, stripes: int):
        self.codec = ecreg.instance().factory("tpu", dict(profile))
        self.backend = JaxBackend()
        self.backend.configure_mesh(1, 0)   # tier-1 has 8 fake devices
        self.codec.core.backend = self.backend
        self.unit = unit
        self.n = int(profile["k"]) + int(profile["m"])
        obj = np.random.default_rng([34, self.n]).bytes(
            stripes * int(profile["k"]) * unit)
        self.shards = [
            np.frombuffer(s, np.uint8).reshape(stripes, unit)
            for s in reference(ref).shards_of(obj, profile, unit)]

    def decode(self, lost) -> dict:
        present = {i: self.shards[i] for i in range(self.n)
                   if i not in lost}
        return self.codec.decode_batch_async(present, self.unit).wait()


@pytest.fixture(scope="module")
def pallas_serves():
    """What a TPU host chooses, chosen here, with its programs built
    for the Pallas interpreter."""
    def interpreted(kernel, w, packetsize=0, donate=False):
        return ROWS_PROGRAM(kernel, w, packetsize, donate, interpret=True)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(je, "rows_program", interpreted)
        mp.setattr(je, "gf8_kernel", lambda: "gf_mxu_pallas")
        mp.setattr(je, "packet_kernel", lambda ps: "packet_mxu_pallas")
        mp.setattr(JaxBackend, "gf8_fast_path", lambda self: True)
        yield


@pytest.fixture(scope="module")
def cauchy(pallas_serves):
    return Served(CAUCHY, CAUCHY_UNIT, "cauchy_good_w8", stripes=1)


@pytest.fixture(scope="module")
def rs(pallas_serves):
    return Served(RS, RS_UNIT, "reed_sol_van_w8", stripes=2)


def sid(lost) -> str:
    return "lost" + "_".join(str(i) for i in lost)


# -- (a) every signature, against the plain references -----------------------
@pytest.mark.parametrize("lost", list(itertools.combinations(range(14), 2)),
                         ids=sid)
def test_cauchy_k10m4_two_erasures_equal_the_reference(cauchy, lost):
    got = cauchy.decode(lost)
    assert sorted(got) == list(lost)
    for e in lost:
        assert np.array_equal(got[e], cauchy.shards[e]), e
    assert cauchy.backend.kernel_calls.get("packet_xor_chain", 0) == 0


@pytest.mark.parametrize(
    "lost", [c for r in (1, 2) for c in itertools.combinations(range(6), r)],
    ids=sid)
def test_reed_sol_van_k4m2_erasures_equal_the_reference(rs, lost):
    got = rs.decode(lost)
    assert sorted(got) == list(lost)
    for e in lost:
        assert np.array_equal(got[e], rs.shards[e]), e
    assert set(rs.backend.kernel_calls) == {"gf_mxu_pallas"}


# -- (b) programs follow shapes, not signatures ------------------------------
def test_all_91_cauchy_signatures_run_one_program(pallas_serves):
    """91 row sets of one shape [16, 80] at one input shape: bound 91
    times, one executable."""
    served = Served(CAUCHY, CAUCHY_UNIT, "cauchy_good_w8", stripes=1)
    for lost in itertools.combinations(range(14), 2):
        served.decode(lost)
    be = served.backend
    assert (be.row_sets_bound, be.row_programs_built) == (91, 1)
    bound = [fn for key, fn in be._chain_lru._d.items() if key[0] == "pkt"]
    assert len(bound) == 91 and all(isinstance(fn, BoundRows)
                                    and fn.bits.shape == (16, 80)
                                    and fn.calls == 1 for fn in bound)
    assert len({id(fn.program) for fn in bound}) == 1


@pytest.mark.parametrize("family,n_lost", [("cauchy", 1), ("cauchy", 3),
                                           ("rs", 1), ("rs", 2)])
def test_distinct_signatures_lower_one_program_a_shape(
        pallas_serves, family, n_lost):
    """N signatures of one row-set shape at an input shape nothing
    else in this file dispatches: JAX lowers at most one program."""
    profile, unit, ref = {"cauchy": (CAUCHY, 2 * CAUCHY_UNIT,
                                     "cauchy_good_w8"),
                          "rs": (RS, 3 * RS_UNIT, "reed_sol_van_w8")}[family]
    served = Served(profile, unit, ref, stripes=3)
    signatures = list(itertools.combinations(range(served.n), n_lost))[:12]
    before = LOWERED[0]
    for lost in signatures:
        got = served.decode(lost)
        for e in lost:
            assert np.array_equal(got[e], served.shards[e]), (lost, e)
    assert LOWERED[0] - before <= 1, \
        f"{LOWERED[0] - before} programs lowered for " \
        f"{len(signatures)} signatures of one shape"
    be = served.backend
    # the cache keys on the rows: with the all-ones first parity row,
    # losing any one data chunk is the same row set
    assert 1 < be.row_sets_bound <= len(signatures)
    assert be.row_programs_built == 1


def test_a_second_row_set_shape_is_a_second_program(cauchy):
    """One lost chunk of 14 is [8, 80] rows: another executable, and
    one only, whichever chunk it is."""
    built = cauchy.backend.row_programs_built
    for lost in ((0,), (5,), (13,)):
        got = cauchy.decode(lost)
        assert np.array_equal(got[lost[0]], cauchy.shards[lost[0]])
    assert cauchy.backend.row_programs_built == built + 1


def test_encode_and_a_k_shard_decode_share_their_program(cauchy):
    """The pool's coding bit-matrix and the recovery rows of a read
    that gathered k of k+m shards are both [m*w, k*w]: at one input
    shape they are one executable (what the degraded cells dispatch)."""
    be = cauchy.backend
    data = np.stack(cauchy.shards[:10], axis=1)         # [1, 10, L]
    parity = cauchy.codec.encode_batch_async(data).wait()
    for j in range(4):
        assert np.array_equal(parity[:, j], cauchy.shards[10 + j])
    built = be.row_programs_built
    have = [i for i in range(14) if i not in (0, 1, 12, 13)]
    got = cauchy.codec.decode_batch_async(
        {i: cauchy.shards[i] for i in have}, CAUCHY_UNIT).wait()
    assert sorted(got) == [0, 1, 12, 13]    # every absent chunk
    for e in got:
        assert np.array_equal(got[e], cauchy.shards[e]), e
    assert be.row_programs_built == built


def test_off_a_tpu_the_xor_schedule_is_a_program_a_signature(monkeypatch):
    """What stays: the static XOR chain is unrolled from a row set's
    ones, so on a CPU each signature is a binding without bits and an
    executable of its own, and the counters say so."""
    codec = ecreg.instance().factory("tpu", dict(CAUCHY))
    be = codec.core.backend = JaxBackend()
    monkeypatch.setattr(je, "packet_kernel", PACKET_KERNEL)
    rng = np.random.default_rng(35)
    shards = {i: rng.integers(0, 256, (1, CAUCHY_UNIT), dtype=np.uint8)
              for i in range(2, 14)}
    for lost in ((2,), (3,)):
        present = {i: c for i, c in shards.items() if i not in lost}
        # survivors only: chunks 0 and 1 are absent as well
        codec.decode_batch_async(present, CAUCHY_UNIT).wait()
    assert be.kernel_calls == {"packet_xor_chain": 2}
    assert (be.row_sets_bound, be.row_programs_built) == (2, 2)
    assert all(fn.bits is None for fn in be._chain_lru._d.values())
