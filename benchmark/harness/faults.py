"""Faults planted under the timed path, for the control and the tests.

The benchmark's own runs never plant one.  Each breaks a guarantee the
configuration states, at the place where the answer is produced, by
patching the program from outside (no option of the program is used or
added); each returns the call that takes its patch out again.
"""
import numpy as np


def _one_bit_off(fn, bit: int):
    """The compiled program ``fn`` with one bit of its output flipped."""
    def altered(x):
        out = fn(x)
        first = (0,) * out.ndim
        return out.at[first].set(out[first] ^ bit)
    return altered


def _one_bit_off_host(out, bit: int):
    """A lane's output on the host with one bit flipped."""
    if isinstance(out, np.ndarray) and out.dtype == np.uint8 \
            and out.ndim >= 3 and out.size:
        out = np.array(out)
        out.reshape(-1)[0] ^= bit
    return out


def _alter_kernel_output():
    """Every GF dispatch returns one flipped bit: stored parity and
    reconstructed chunks are no longer the code's.  Planted at the
    places the lanes' outputs pass: the compiled kernels that
    ``gf8_fn`` (every byte-domain w=8 dispatch on a TPU, the
    synchronous decode path included) and ``packet_chain_fn`` (every
    synchronous dispatch of a packet-layout code on a TPU) hand out,
    ``apply_packet_chunks`` (the same off a TPU, where no chain is
    compiled) and ``AsyncBatch.wait`` (the async lanes).  Different
    bits, so that no two cancel."""
    from ceph_tpu.ops import jax_engine
    orig_wait = jax_engine.AsyncBatch.wait
    orig_fn = jax_engine.JaxBackend.gf8_fn
    orig_pkt = jax_engine.JaxBackend.packet_chain_fn
    orig_chunks = jax_engine.JaxBackend.apply_packet_chunks

    def wait(self):
        return _one_bit_off_host(orig_wait(self), 2)

    def apply_packet_chunks(self, *args, **kwargs):
        return _one_bit_off_host(orig_chunks(self, *args, **kwargs), 8)

    def gf8_fn(self, *args, **kwargs):
        return _one_bit_off(orig_fn(self, *args, **kwargs), 1)

    def packet_chain_fn(self, *args, **kwargs):
        return _one_bit_off(orig_pkt(self, *args, **kwargs), 4)

    jax_engine.AsyncBatch.wait = wait
    jax_engine.JaxBackend.gf8_fn = gf8_fn
    jax_engine.JaxBackend.packet_chain_fn = packet_chain_fn
    jax_engine.JaxBackend.apply_packet_chunks = apply_packet_chunks

    def undo():
        jax_engine.AsyncBatch.wait = orig_wait
        jax_engine.JaxBackend.gf8_fn = orig_fn
        jax_engine.JaxBackend.packet_chain_fn = orig_pkt
        jax_engine.JaxBackend.apply_packet_chunks = orig_chunks
    return undo


def _alter_read_reply():
    """The primary flips one bit of every read payload of a stripe or
    more as it builds the reply: a read no longer returns the last
    acknowledged bytes."""
    from ceph_tpu.osd import pg
    orig = pg.PG._reply

    def _reply(self, conn, msg, result, out_data, extra=None):
        if result == 0 and out_data and len(out_data[0]) >= 4096:
            first = bytearray(out_data[0])
            first[0] ^= 1
            out_data = [bytes(first)] + list(out_data[1:])
        return orig(self, conn, msg, result, out_data, extra)
    pg.PG._reply = _reply
    return lambda: setattr(pg.PG, "_reply", orig)


FAULTS = {
    "kernel_output_altered": _alter_kernel_output,
    "read_reply_altered": _alter_read_reply,
}

def control_for(cell) -> str:
    """Healthy reads are controlled at the reply; everything that
    rides a GF lane (writes, overwrites, reads that reconstruct) at
    the kernel's output."""
    kinds = {o["op"] for o in cell.traffic["ops"]}
    if kinds == {"read"} and not cell.state.get("after_populate"):
        return "read_reply_altered"
    return "kernel_output_altered"


class Planter:
    """``run_cell``'s ``plant`` hook for one fault; ``undo`` takes the
    patch out again, planted or not."""

    def __init__(self, name: str):
        self.name = name
        self._undo = []

    def __call__(self, dep) -> None:
        self._undo.append(FAULTS[self.name]())

    def undo(self) -> None:
        while self._undo:
            self._undo.pop()()
