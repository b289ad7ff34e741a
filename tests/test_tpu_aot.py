"""The Pallas kernels through the real TPU compiler, without a TPU.

libtpu can describe a v5e topology on a host that has no chip, and
``jit(f).lower(...).compile()`` against its devices runs XLA:TPU and
Mosaic for real.  Nothing executes, so this says nothing about results
(the interpret-mode tests in test_tpu_plugin.py guard the arithmetic)
— but a block shape Mosaic refuses, a VMEM overrun or a shard_map
wrapper that does not trace fails HERE, in tier-1, instead of on the
next chip run, where the batcher's twin would have hidden it.
Shapes are the ones chip_smoke.py and the OSD batcher dispatch.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from ceph_tpu.ec import registry as ecreg
from ceph_tpu.ops import jax_engine as je
from ceph_tpu.ops.matrix import (matrix_to_bitmatrix,
                                 reed_sol_vandermonde_coding_matrix)


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # no libtpu here: nothing to compile with
        pytest.skip(f"no TPU compiler on this host: {e!r}")
    return topo.devices


def compile_for(program, bits_shape, shape, sharding):
    """Lower and compile a rows_program for the described chip, the
    row set an operand of ``bits_shape`` as the backend binds it."""
    bits = jax.ShapeDtypeStruct(bits_shape, jnp.int8, sharding=sharding)
    arg = jax.ShapeDtypeStruct(shape, jnp.uint8, sharding=sharding)
    return program.lower(bits, arg).compile()


@pytest.mark.parametrize("shape", [
    (128, 8, 4096),              # one 4 MiB object at the 4 KiB unit
    (64, 8, 131072),             # 1 MiB stripes
])
def test_gf_mxu_kernel_compiles_for_v5e(v5e, shape):
    compile_for(je.rows_program("gf_mxu_pallas", 8), (32, 64), shape,
                SingleDeviceSharding(v5e[0]))


@pytest.mark.parametrize("rows,k,batch", [(2, 4, 64), (1, 4, 64),
                                          (4, 4, 64)])
def test_gf_mxu_kernel_compiles_at_the_degraded_cells_shapes(v5e, rows,
                                                             k, batch):
    """k4m2.degraded_read_4m: recovery rows of a read that gathered k
    of 6 shards (2 rows, the encode matrix's shape), a single row, and
    a square set with its input donated, at the 4 KiB unit."""
    compile_for(je.rows_program("gf_mxu_pallas", 8, donate=rows == k),
                (8 * rows, 8 * k), (batch, k, 4096),
                SingleDeviceSharding(v5e[0]))


@pytest.mark.slow
def test_gf_mxu_kernel_compiles_at_the_block_cap(v5e):
    """4 MiB stripes: _pick_block_len hits its 1<<19 cap, the largest
    VMEM block the kernel ever asks for (~15 s of compile)."""
    compile_for(je.rows_program("gf_mxu_pallas", 8), (32, 64),
                (8, 8, 524288), SingleDeviceSharding(v5e[0]))


def test_packet_mxu_kernel_compiles_for_v5e(v5e):
    """cauchy_good k=10 m=4 at 4 MiB stripes (BASELINE config 3)."""
    cg = ecreg.instance().factory(
        "jerasure", {"k": "10", "m": "4", "technique": "cauchy_good"})
    core = cg.core
    compile_for(
        je.rows_program("packet_mxu_pallas", core.w, core.packetsize),
        core.bitmatrix.shape, (8, 10, cg.get_chunk_size(4 << 20)),
        SingleDeviceSharding(v5e[0]))


@pytest.mark.parametrize("rows,batch", [(32, 8), (32, 128), (16, 8),
                                        (8, 1024)])
def test_packet_mxu_kernel_compiles_at_the_served_shapes(v5e, rows, batch):
    """The Cauchy cells' dispatches: cauchy_good k=10 m=4
    packetsize=2048 at the 64 KiB chunk (4 regions), one object (7
    stripes staged as 8) and the largest group the load forms (128).
    ``rows`` 32 is the encode bit-matrix AND the recovery rows of a
    read that gathered 10 of 14 shards (cauchy_k10m4.degraded_read_4m:
    one program for both, whatever the signature), 16 and 8 the rows
    of two chunks and one (a caller that hands in more than k)."""
    compile_for(je.rows_program("packet_mxu_pallas", 8, 2048),
                (rows, 80), (batch, 10, 65536),
                SingleDeviceSharding(v5e[0]))


def test_sharded_rows_fn_compiles_for_a_v5e_2x2_mesh(v5e, monkeypatch):
    """The production mesh dispatch with the kernel a TPU host picks:
    shard_map around the pallas_call, with and without donation."""
    from ceph_tpu.parallel import mesh as pmesh
    monkeypatch.setattr(je, "gf8_kernel", lambda: "gf_mxu_pallas")
    mesh = Mesh(np.array(v5e).reshape(2, 2), ("dp", "sp"))
    sharding = NamedSharding(mesh, P("dp", None, "sp"))
    for rows, donate in (
            (reed_sol_vandermonde_coding_matrix(8, 4, 8), False),
            (reed_sol_vandermonde_coding_matrix(2, 2, 8), True)):
        fn = pmesh.sharded_rows_fn(mesh, rows, donate=donate)
        arg = jax.ShapeDtypeStruct((1024, rows.shape[1], 4096),
                                   jnp.uint8, sharding=sharding)
        out = fn.lower(arg).compile().output_shardings
        assert out.spec == P("dp", None, "sp")
