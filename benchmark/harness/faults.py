"""Faults planted under the timed path, for the control and the tests.

The benchmark's own runs never plant one.  Each breaks a guarantee the
configuration states, at the place where the answer is produced, by
patching the program from outside (no option of the program is used or
added); each returns the call that takes its patch out again.
"""
import numpy as np


def _alter_kernel_output():
    """Every GF dispatch returns one flipped bit: stored parity and
    reconstructed chunks are no longer the code's.  Planted at the two
    places the lanes' outputs pass: the compiled kernel ``gf8_fn``
    hands out (every w=8 dispatch on a TPU, the synchronous decode
    path included) and ``AsyncBatch.wait`` (the async lanes, which is
    all there is on a CPU).  Different bits, so that the two never
    cancel."""
    from ceph_tpu.ops import jax_engine
    orig_wait = jax_engine.AsyncBatch.wait
    orig_fn = jax_engine.JaxBackend.gf8_fn

    def wait(self):
        out = orig_wait(self)
        if isinstance(out, np.ndarray) and out.dtype == np.uint8 \
                and out.ndim >= 3 and out.size:
            out = np.array(out)
            out.reshape(-1)[0] ^= 2
        return out

    def gf8_fn(self, *args, **kwargs):
        fn = orig_fn(self, *args, **kwargs)

        def altered(x):
            out = fn(x)
            first = (0,) * out.ndim
            return out.at[first].set(out[first] ^ 1)
        return altered

    jax_engine.AsyncBatch.wait = wait
    jax_engine.JaxBackend.gf8_fn = gf8_fn

    def undo():
        jax_engine.AsyncBatch.wait = orig_wait
        jax_engine.JaxBackend.gf8_fn = orig_fn
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
