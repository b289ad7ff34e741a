"""Non-regression corpus + CRC32C + bench sweep tests.

Reference analog: encode-decode-non-regression.sh over the
ceph-erasure-code-corpus (bit-exact chunks across builds),
src/common/crc32c.cc (Castagnoli with hardware dispatch; RFC 3720
test vector), qa/workunits/erasure-code/bench.sh sweep format."""
import json
import os
import subprocess
import sys

import pytest

from ceph_tpu.tools import bench_sweep, ec_non_regression
from ceph_tpu.utils import crc as crcmod
from ceph_tpu.utils.crc import (available_native, crc32c,
                                crc32c_blocks, _py_crc32c)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CORPUS = os.path.join(REPO, "corpus")


def test_committed_corpus_is_bit_exact():
    """THE compatibility gate: every codec must reproduce the
    committed chunks byte-for-byte and decode every recoverable 1-
    and 2-erasure pattern back to them."""
    assert ec_non_regression.check(CORPUS) == 0


def test_corpus_detects_divergence(tmp_path):
    """A corrupted stored chunk must fail the check (the check is
    real, not vacuous)."""
    base = str(tmp_path / "c")
    assert ec_non_regression.create(base) == 0
    victim_dir = ec_non_regression.config_dir(
        base, "jerasure", {"k": "2", "m": "1",
                           "technique": "reed_sol_van"})
    path = os.path.join(victim_dir, "chunk.0")
    blob = bytearray(open(path, "rb").read())
    blob[100] ^= 0xFF
    open(path, "wb").write(bytes(blob))
    assert ec_non_regression.check(base) == 1


def test_payload_is_deterministic():
    assert ec_non_regression.payload() == ec_non_regression.payload()
    assert len(ec_non_regression.payload()) == \
        ec_non_regression.PAYLOAD_SIZE


# ---------------------------------------------------------------- crc


def test_crc32c_rfc3720_vector():
    # RFC 3720 B.4: crc32c("123456789") == 0xE3069283
    assert crc32c(b"123456789") == 0xE3069283
    assert _py_crc32c(b"123456789", 0) == 0xE3069283


def test_crc32c_chaining_and_native_parity():
    data = os.urandom(100_000)
    whole = crc32c(data)
    part = crc32c(data[50_000:], crc32c(data[:50_000]))
    assert whole == part
    assert _py_crc32c(data, 0) == whole  # python == native
    assert crc32c(b"") == 0


def test_native_crc_kernel_builds():
    """The image ships g++; the native kernel must actually build
    (the pure-python fallback is for compilerless environments)."""
    assert available_native()


def _corpus_chunks():
    out = []
    for root, _dirs, files in sorted(os.walk(CORPUS)):
        out += [os.path.join(root, f) for f in sorted(files)
                if f.startswith("chunk.")]
    assert out
    return out


@pytest.mark.parametrize("wrap", [bytearray, memoryview, bytes],
                         ids=["bytearray", "memoryview", "bytes"])
@pytest.mark.parametrize("native", [True, False],
                         ids=["native", "python"])
def test_crc32c_blocks_equals_crc32c_block_by_block(native, wrap,
                                                    monkeypatch):
    """One call over n blocks gives what n calls give, on every chunk
    of the committed corpus, through the library and through the
    fallback a host without a compiler takes, whatever buffer the
    caller holds."""
    if native:
        assert available_native()
    else:
        monkeypatch.setattr(crcmod, "_lib", None)
        monkeypatch.setattr(crcmod, "_tried", True)
    # the fallback runs a byte at a time: a few chunks, small blocks
    paths = _corpus_chunks() if native else _corpus_chunks()[:3]
    for block_len in (4096, 512) if native else (512,):
        for path in paths:
            with open(path, "rb") as f:
                blob = f.read()
            blob = blob[:len(blob) // block_len * block_len]
            if not native:
                blob = blob[:8 * block_len]
            want = [_py_crc32c(blob[i:i + block_len], 0) if not native
                    else crc32c(blob[i:i + block_len])
                    for i in range(0, len(blob), block_len)]
            got = crc32c_blocks(wrap(blob), block_len)
            assert list(got) == want, (path, block_len)
    assert crc32c_blocks(wrap(b""), 4096) == []
    assert crc32c_blocks(wrap(b"123456789"), 9) == [0xE3069283]
    with pytest.raises(ValueError):
        crc32c_blocks(wrap(b"x" * 10), 4)


def test_crc32c_blocks_hot_path_takes_no_loader_lock(monkeypatch):
    """Once the library is loaded a call does not pass through the
    loader's lock, and it leaves no export on the caller's buffer."""
    assert available_native()
    buf = bytearray(os.urandom(8 * 4096))

    class _NoLock:
        def __enter__(self):
            raise AssertionError("loader lock taken on the hot path")

        def __exit__(self, *a):
            return False
    monkeypatch.setattr(crcmod, "_lock", _NoLock())
    want = crc32c_blocks(buf, 4096)
    buf[4096] ^= 1
    got = crc32c_blocks(buf, 4096)
    assert got[1] != want[1] and got[:1] + got[2:] == want[:1] + want[2:]
    buf.extend(b"\x00" * 4096)          # BufferError under a live export
    assert crc32c_blocks(buf, 4096)[:8] == got


# -------------------------------------------------------------- sweep


def test_bench_sweep_rows(capsys):
    assert bench_sweep.main(["--plugins", "jerasure", "--km", "2/1",
                             "--techniques", "reed_sol_van",
                             "--size", str(64 << 10), "-i", "1",
                             "--workloads", "encode"]) == 0
    rows = [json.loads(ln) for ln in
            capsys.readouterr().out.splitlines() if ln.strip()]
    assert len(rows) == 1
    r = rows[0]
    assert r["plugin"] == "jerasure" and r["k"] == 2 and r["gbps"] > 0


def test_bench_sweep_html(tmp_path, capsys):
    out = str(tmp_path / "sweep.html")
    assert bench_sweep.main(["--plugins", "jerasure", "--km", "2/1",
                             "--techniques", "reed_sol_van",
                             "--size", str(64 << 10), "-i", "1",
                             "--workloads", "encode",
                             "--html", out]) == 0
    capsys.readouterr()
    html = open(out).read()
    assert "GB/s" in html and "jerasure" in html
