"""chip_smoke.py on the CPU: it refuses to run, and its phases still work.

The smoke itself only means something on a TPU (the driver runs it
there on every PR).  Tier-1 holds the two things a CPU can: the script
exits non-zero before any work when JAX finds no TPU, and its phase
functions run at toy size against the CPU backend — so a rename in the
package breaks tier-1 here and not the next chip run.
"""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def test_refuses_without_a_tpu():
    """Non-zero, no result line, and before a cluster boots (a cluster
    could not boot inside this timeout's margin unnoticed: its mon logs
    to stderr)."""
    out = subprocess.run([sys.executable,
                          os.path.join(REPO, "chip_smoke.py")],
                         capture_output=True, text=True, timeout=120,
                         cwd=REPO,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode != 0
    assert "chip_smoke needs a TPU" in out.stderr
    assert '"ok"' not in out.stdout
    assert "mon.0" not in out.stderr


def test_phase_device_reports_what_jax_reports():
    import jax
    with pytest.raises(RuntimeError, match="needs a TPU"):
        chip_smoke.phase_device()
    dev = chip_smoke.phase_device(require_tpu=False)
    assert dev == {"platform": "cpu",
                   "kind": jax.devices()[0].device_kind,
                   "count": len(jax.devices())}


def test_phase_codec_toy():
    geometries = (
        ("rs", {"technique": "reed_sol_van", "k": "4", "m": "2",
                "w": "8"}, 8, 16 << 10, (1, 4)),
        ("cauchy", {"technique": "cauchy_good", "k": "3", "m": "2",
                    "packetsize": "128"}, 2, 24 << 10, (0, 3)),
        ("w16", {"technique": "reed_sol_van", "k": "4", "m": "2",
                 "w": "16"}, 4, 16 << 10, (0, 5)),
    )
    rep = chip_smoke.phase_codec(3, geometries=geometries,
                                 served_batches=(8, 4))
    assert set(rep) >= {"rs", "cauchy", "w16"}
    # on the CPU every family is served by an XLA kernel, and says so
    assert rep["rs"]["kernel"] == ["bitplane_xla"]
    assert rep["cauchy"]["kernel"] == ["packet_bitplane_xla"]
    assert rep["rs"]["reference_backend"] == "numpy"
    # every family chip_smoke checks on the TPU has a promised kernel
    for _name, prof, *_ in chip_smoke.CODEC_GEOMETRIES:
        assert f"{prof['technique']}/{prof.get('w', '8')}" in \
            chip_smoke.TPU_KERNEL


def test_phase_cluster_toy():
    """The whole served-path walk — write, read back, overwrites, OSD
    loss, rebuild, deep scrub, counters over the admin path — on a
    4-OSD k=2 m=1 pool.  The lane checks hold on the CPU too: with
    ec_tpu_fallback_cpu=false every group goes to the JAX backend."""
    rep = chip_smoke.phase_cluster(
        5, n_osds=4, k=2, m=1, n_objs=6, obj_bytes=256 << 10,
        n_overwrites=12, n_degraded=3, depth=4, pg_num=8)
    for lane in ("encode", "decode", "delta"):
        assert rep["lanes"][lane]["reqs"] > 0
        assert rep["lanes"][lane]["twin_reqs"] == 0
    assert rep["ec_batcher"]["device_reqs"] > 0
    # the conftest's 8 virtual devices: the mesh is the data plane
    assert rep["mesh_devices"] == 8
