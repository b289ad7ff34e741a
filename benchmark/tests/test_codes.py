"""The erasure code comes from the configuration: a plain reference per
code found by name, the pool's real stripe width, packet programs
warmed and controlled.  Runnable by hand on a CPU:

    python3 -m pytest benchmark/tests/test_codes.py -q       (~1 min)

The last tests rehearse a deployment that has no cell yet: a
``cauchy_good`` pool (tests/configs/ec_cauchy_k4m3_ps512_7osd.json)
driven through ``run.run_cell`` as test_control.py drives the real
cells, with the benchmark description extended in memory.
"""
import argparse
import copy
import itertools
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import run  # noqa: E402
from harness import deploy, faults, spec  # noqa: E402

CAUCHY_FILE = "benchmark/tests/configs/ec_cauchy_k4m3_ps512_7osd.json"
CAUCHY_CELL = "cauchy_k4m3.write_4m"


def bench_with_cauchy(file: str = CAUCHY_FILE) -> dict:
    """BENCHMARK.json with the test-only deployment and one cell on it,
    in memory only."""
    bench = copy.deepcopy(spec.benchmark())
    bench["configs"].append({"name": "ec_cauchy_k4m3_ps512_7osd",
                             "file": file})
    bench["workloads"].append({
        "name": CAUCHY_CELL, "config": "ec_cauchy_k4m3_ps512_7osd",
        "traffic": "radosbench_write_4m_qd16", "chips": 1})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if "k8m4.write_4m" in metric.get("workloads", []):
            metric["workloads"].append(CAUCHY_CELL)
    return bench


def rehearse(bench: dict, plant=None, seed: int = 2147483693) -> dict:
    args = argparse.Namespace(workload=CAUCHY_CELL, seed=seed, seconds=2.0,
                              trace=0, rehearsal=True, record_trace=None)
    result = run.run_cell(args, plant=plant, bench=bench)
    result.pop("_info")
    return result


# -- (a) the Cauchy reference against this repo's CPU port ----------------
def cpu_port_shards(obj: bytes, k, m, su, packetsize) -> list:
    from ceph_tpu.ec import registry
    codec = registry.instance().factory("jerasure", {
        "k": str(k), "m": str(m), "technique": "cauchy_good",
        "packetsize": str(packetsize)})
    data = np.frombuffer(obj, np.uint8).reshape(-1, k, su)
    parity = codec.core.encode_batch(data)
    return [np.ascontiguousarray(a[:, i]).tobytes()
            for a, n in ((data, k), (parity, m)) for i in range(n)]


@pytest.mark.parametrize("k,m,packetsize,su", [(4, 3, 512, 4096),
                                               (10, 4, 2048, 65536)])
def test_cauchy_reference_equals_the_cpu_port(k, m, packetsize, su):
    cauchy = spec.reference("cauchy_good_w8")
    obj = np.random.default_rng([k, m, 2147483659]).bytes(3 * k * su)
    profile = {"technique": "cauchy_good", "k": k, "m": m, "w": 8,
               "packetsize": packetsize}
    got = cauchy.shards_of(obj, profile, su)
    assert len(got) == k + m and all(len(s) == 3 * su for s in got)
    assert got == cpu_port_shards(obj, k, m, su, packetsize)


def _gf2_rank(rows: np.ndarray) -> int:
    a = rows.copy() % 2
    rank = 0
    for c in range(a.shape[1]):
        hit = np.flatnonzero(a[rank:, c])
        if not hit.size:
            continue
        p = rank + hit[0]
        a[[rank, p]] = a[[p, rank]]
        below = np.flatnonzero(a[:, c])
        below = below[below != rank]
        a[below] ^= a[rank]
        rank += 1
        if rank == a.shape[0]:
            break
    return rank


def test_cauchy_bitmatrix_is_mds_at_k10m4():
    """Any k of the k+m chunks determine the object: the k*w rows of
    [I; B] that k chunks keep are invertible over GF(2).  Every single
    and double erasure, and a seeded sample of the triple and
    quadruple ones."""
    cauchy = spec.reference("cauchy_good_w8")
    k, m, w = 10, 4, 8
    bits = cauchy.coding_bitmatrix(k, m)
    assert bits.shape == (m * w, k * w)
    assert cauchy.coding_matrix(k, m)[0] == [1] * k
    full = np.vstack([np.eye(k * w, dtype=np.uint8), bits])
    chunks = range(k + m)
    lost_sets = [c for n in (1, 2) for c in itertools.combinations(chunks, n)]
    rng = np.random.default_rng(2147483659)
    for n in (3, 4):
        every = list(itertools.combinations(chunks, n))
        lost_sets += [every[i] for i in
                      rng.choice(len(every), size=60, replace=False)]
    for lost in lost_sets:
        keep = [c for c in chunks if c not in lost][:k]
        rows = np.concatenate([full[c * w:(c + 1) * w] for c in keep])
        assert _gf2_rank(rows) == k * w, lost


def test_cauchy_reference_refuses_what_it_does_not_reproduce():
    cauchy = spec.reference("cauchy_good_w8")
    with pytest.raises(ValueError, match="cbest"):
        cauchy.coding_matrix(4, 2)
    with pytest.raises(ValueError, match="regions"):
        cauchy.stripe_shards(bytes(4 * 4096), 4, 3, 4096, 2048)


# -- (b) the reference follows from the configuration's profile -------------
def test_reference_by_profile():
    for row in spec.benchmark()["configs"]:
        with open(os.path.join(spec.ROOT, row["file"])) as fh:
            assert spec.reference_name(json.load(fh)) == "reed_sol_van_w8"
    with open(os.path.join(spec.ROOT, CAUCHY_FILE)) as fh:
        assert spec.reference_name(json.load(fh)) == "cauchy_good_w8"
    assert hasattr(spec.reference("reed_sol_van_w8"), "shards_of")
    with pytest.raises(SystemExit, match="reed_sol_van_w8"):
        spec.reference("liberation_w7")
    with pytest.raises(ValueError, match="does not serve"):
        spec.reference("reed_sol_van_w8").shards_of(
            bytes(4 * 4096), {"technique": "cauchy_good", "k": 4, "m": 2,
                              "w": 8}, 4096)


def test_no_reference_imports_the_program():
    d = os.path.join(spec.BENCH_DIR, "references")
    for f in sorted(os.listdir(d)):
        if f.endswith(".py"):
            with open(os.path.join(d, f)) as fh:
                src = fh.read()
            assert "import ceph_tpu" not in src and \
                "from ceph_tpu" not in src, f


class _Store:
    def __init__(self, shards):
        self.shards = shards

    def read(self, coll, shard):
        return self.shards[shard]


def test_stored_shards_asks_the_configurations_reference_and_pads():
    """An object that ends inside a stripe is stored padded with zeros
    to the stripe's end (a 4 MiB object on a 640 KiB stripe)."""
    from harness import check
    with open(os.path.join(spec.ROOT, CAUCHY_FILE)) as fh:
        config = json.load(fh)
    k, m, su = 4, 3, 4096
    obj = np.random.default_rng(2147483659).bytes(2 * k * su + 100)
    padded = obj + bytes(k * su - 100)
    store = _Store(spec.reference("cauchy_good_w8").shards_of(
        padded, config["pool"]["profile"], su))
    dep = argparse.Namespace(
        k=k, m=m, stripe_unit=su, config=config, dead=set(),
        shard_index=lambda names: {(names[0], s): [(s, store, None, s)]
                                   for s in range(k + m)})
    model = argparse.Namespace(name=lambda n: f"obj{n}",
                               current=lambda n: obj)
    assert check.stored_shards(dep, model, [0]) == (0, 0)
    store.shards[5] = bytes(len(store.shards[5]))
    del dep.shard_index
    dep.shard_index = lambda names: {
        (names[0], s): [(s, store, None, s)] for s in range(k + m - 1)}
    assert check.stored_shards(dep, model, [0]) == (1, 1)


# -- (c) the deployment is what the file states -----------------------------
class _Pool:
    stripe_width = 4 * 4096


class _Map:
    def get_pool(self, name):
        return _Pool()


def test_stripe_width_guard():
    dep = deploy.Deployment.__new__(deploy.Deployment)
    dep.config = {"name": "made_up", "pool": {"name": "benchpool"}}
    dep.k = 4
    dep.rad = argparse.Namespace(
        objecter=argparse.Namespace(osdmap=_Map()))
    dep.stripe_unit = 4096
    dep.check_stripe_width()            # 4 * 4096: as stated
    dep.stripe_unit = 8192
    with pytest.raises(SystemExit, match="16384.*32768"):
        dep.check_stripe_width()


# -- (3) every GF program is warmed, (d) and controlled ---------------------
def _packet_program():
    from ceph_tpu.ec.plugins.tpu import shared_backend
    cauchy = spec.reference("cauchy_good_w8")
    bits = cauchy.coding_bitmatrix(4, 3)
    return shared_backend(), bits


def test_packet_chains_are_warmed_by_chain_keys():
    layouts = spec.chain_keys()
    assert set(layouts) == {"gf8", "gf8don", "pkt"}
    coeffs = ((1, 1, 1, 1, 1), (1, 2, 4, 8, 16))
    assert deploy.chain_columns(("gf8", coeffs), layouts["gf8"]) == 5
    assert deploy.chain_columns(("gf8don", coeffs), layouts["gf8don"]) == 5
    backend, bits = _packet_program()
    backend.packet_chain_fn(bits, 8, 512)
    dep = deploy.Deployment.__new__(deploy.Deployment)
    dep.k, dep.stripe_unit = 4, 4096
    traffic = {"depth": 2, "ops": [{"op": "write_full",
                                    "io_bytes": 2 * 4 * 4096}]}
    before = dict(backend.kernel_calls)
    # one chain, batches of 2 and 4 stripes
    assert dep.warm_cached_programs(traffic) >= 2
    with backend._chain_lru._lock:
        keys = [k for k in backend._chain_lru._d if k[0] == "pkt"]
    assert deploy.chain_columns(keys[0], layouts["pkt"]) == 4
    assert backend.kernel_calls == before       # run, not rebuilt


def test_planted_fault_flips_one_bit_of_a_packet_program():
    import jax.numpy as jnp
    backend, bits = _packet_program()
    x = jnp.asarray(np.random.default_rng(2147483659).integers(
        0, 256, size=(2, 4, 4096), dtype=np.uint8))
    sound = np.asarray(backend.packet_chain_fn(bits, 8, 512)(x))
    plant = faults.Planter("kernel_output_altered")
    plant(None)
    try:
        broken = np.asarray(backend.packet_chain_fn(bits, 8, 512)(x))
    finally:
        plant.undo()
    assert sound.shape == broken.shape == (2, 3, 4096)
    diff = np.unpackbits(sound ^ broken)
    assert int(diff.sum()) == 1
    again = np.asarray(backend.packet_chain_fn(bits, 8, 512)(x))
    assert (again == sound).all()
    # and the program's packet layout is the reference's
    cauchy = spec.reference("cauchy_good_w8")
    obj = np.ascontiguousarray(np.asarray(x)).tobytes()
    want = cauchy.stripe_shards(obj, 4, 3, 4096, 512)
    assert [np.ascontiguousarray(sound[:, i]).tobytes()
            for i in range(3)] == want[4:]


# -- (5) the rehearsal --------------------------------------------------------
def test_rehearsal_stripe_width_guard_fires_through_run_cell(tmp_path):
    with open(os.path.join(spec.ROOT, CAUCHY_FILE)) as fh:
        config = json.load(fh)
    config["stripe_unit"] = 8192        # the pool is made with 4096
    wrong = tmp_path / "states_8192.json"
    wrong.write_text(json.dumps(config))
    with pytest.raises(SystemExit, match="16384.*32768"):
        rehearse(bench_with_cauchy(str(wrong)))


@pytest.fixture
def synchronous_encode(monkeypatch):
    """A second witness from the program itself: without the batched
    entry point ``EncodeBatcher.submit`` encodes inline through the
    codec's synchronous ``encode``, which applies the bit-matrix in
    packet layout."""
    from ceph_tpu.ec.plugins import tpu
    monkeypatch.delattr(tpu.TpuCodecMixin, "encode_batch_async")


def test_rehearsal_on_the_synchronous_encode_is_correct(synchronous_encode):
    """The yardstick takes right parity for right: the program's other
    encode path and the plain reference agree on every stored shard of
    a packet-layout pool, through the whole of a run."""
    result = rehearse(bench_with_cauchy())
    assert result["correct"], result["compared"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["compared"]["stored_shards_wrong"]["value"] == 0


def test_rehearsal_control_reaches_the_packet_path(synchronous_encode):
    plant = faults.Planter("kernel_output_altered")
    try:
        result = rehearse(bench_with_cauchy(), plant=plant)
    finally:
        plant.undo()
    assert not result["correct"]
    assert result["compared"]["stored_shards_wrong"]["value"] > 0


def test_rehearsal_of_a_cauchy_pool_reaches_its_last_line():
    """What the served path does to a packet-layout code, seen by the
    yardstick.  The client's read-back has to be right whatever the
    stores hold.  PR 29 read ``stored_shards_wrong`` > 0 here: the
    program's encode lane applies a packet code's bit-matrix in the
    byte domain (PERF.md, section 7).  That is the program's to
    repair, so it is an expected failure and not a red test; once the
    program is repaired this passes as it stands."""
    result = rehearse(bench_with_cauchy())
    compared = {k: v["value"] for k, v in result["compared"].items()}
    print("compared:", json.dumps(compared))
    assert result["rehearsal"] and result["attempted"] > 0
    assert set(compared) == {
        "window_empty", "ops_failed", "readback_objects_wrong",
        "stored_shards_wrong", "stored_shards_missing",
        "lane_requests_on_twin", "device_errors"}
    assert compared["window_empty"] == 0 and compared["ops_failed"] == 0
    assert compared["readback_objects_wrong"] == 0
    assert compared["stored_shards_missing"] == 0
    assert result["correct"] == all(v == 0 for v in compared.values())
    if compared["stored_shards_wrong"]:
        pytest.xfail(f"the served path stores wrong parity for a packet "
                     f"code: {compared}")
