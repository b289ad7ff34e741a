"""The interpreter's budget by layer: the readers of the CPU time that
the sections of a probed nest carry (``benchmark/harness/cpu.py`` and
the seven ``metrics/*.py`` on it), on plain traces written out by
hand."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

from harness import cpu, spec, trace  # noqa: E402

MIB4 = 4 << 20
#: in the order of their entries, appended after decode.solves_per_request
METRICS = ("host.cpu_ms_per_op", "host.offcpu_share", "msgr.cpu_share", "crc.cpu_share", "store.cpu_share",
           "batcher.cpu_share", "pg.cpu_share")


def plain(*threads) -> dict:
    """A 10 s window [1, 11) s; each thread a list of (name, start s,
    wall s, cpu s, keywords), cpu None for a section that does not
    carry it (a nest left unprobed)."""
    lines = [[(trace.WINDOW_SPAN, 1e9, 10e9, {})]]
    for secs in threads:
        line = []
        for name, s, d, cpu_s, meta in secs:
            meta = dict(meta)
            if cpu_s is not None:
                meta["cpu_ns"] = round(cpu_s * 1e9)
            line.append((name, s * 1e9, d * 1e9, meta))
        lines.append(line)
    return {"lines": lines, "device_ops": []}


#: two threads, sections three deep, every nest probed; outermost CPU
#: 0.4 + 0.3 + 0.2 s, thread-busy 1 + 1 + 0.5 s.  Self CPU: reactor.io
#: 0.1, msgr.recv 0.1, msgr.dispatch 0.05, pg.do_op 0.1, crc.host 0.05,
#: batcher.dispatch 0.2, dispatch.h2d 0.1, store.read 0.2
NESTED = (
    [("reactor.io", 2.0, 1.0, 0.4, {"d": "osd.0"}),
     ("msgr.recv", 2.1, 0.2, 0.1, {}),
     ("msgr.dispatch", 2.4, 0.5, 0.2, {}),
     ("pg.do_op", 2.5, 0.2, 0.15, {"op": "c:1"}),
     ("crc.host", 2.55, 0.05, 0.05, {"bytes": 4096})],
    [("batcher.dispatch", 4.0, 1.0, 0.3, {"lane": "enc"}),
     ("dispatch.h2d", 4.1, 0.1, 0.1, {}),
     ("store.read", 6.0, 0.5, 0.2, {})])

#: NESTED and a third thread whose two nests (2.5 s of wall) were left
#: unprobed: thread-busy 5 s, of it 2.5 s probed with 0.9 s of CPU, so
#: all sections took 1.8 s; the shares are NESTED's
SAMPLED = NESTED + (
    [("msgr.send", 3.0, 2.0, None, {}),
     ("crc.host", 3.5, 0.5, None, {}),
     ("store.txn", 8.0, 0.5, None, {})],)

#: cut by both edges: msgr.send counts half of its 0.2 s (its child
#: before the window not at all, the one inside whole), store.txn half
#: of its 0.4 s; thread-busy 0.5 + 0.5 s
CLIPPED = (
    [("msgr.send", 0.5, 1.0, 0.2, {}),
     ("crc.host", 0.6, 0.2, 0.1, {}),
     ("crc.host", 1.2, 0.2, 0.05, {}),
     ("store.txn", 10.5, 1.0, 0.4, {})],)


def stripped(threads):
    """The same sections without ``cpu_ns`` (a program before it)."""
    return tuple([(n, s, d, None, m) for n, s, d, _, m in secs]
                 for secs in threads)


def ctx_of(threads, acked: int = 3) -> dict:
    ops = [{"op": "write_full", "io_bytes": MIB4}]
    window = [(0.0, 1.5 + i, 0, i, 0, 0, 0, MIB4, None)
              for i in range(acked)]
    window.append((0.0, 2.0, 0, 99, 0, 0, -110, 0, None))  # failed
    return {"spans": plain(*threads), "window": window, "ops": ops}


@pytest.mark.parametrize("threads,acked,want", [
    (NESTED, 3, 900.0 / 3),
    (CLIPPED, 3, 300.0 / 3),
    (NESTED, 0, None),                               # no acked op
    (stripped(NESTED), 3, None),                     # the parent
    (SAMPLED, 3, 1800.0 / 3)])                       # scaled by the wall
def test_cpu_ms_per_op(threads, acked, want):
    got = spec.metric_reader("host.cpu_ms_per_op").read(
        ctx_of(threads, acked))
    assert got == want if want is None else got == pytest.approx(want)


@pytest.mark.parametrize("threads,acked,want", [
    (NESTED, 3, 100.0 * (1 - 0.9 / 2.5)),
    (CLIPPED, 3, 100.0 * (1 - 0.3 / 1.0)),
    (NESTED, 0, 100.0 * (1 - 0.9 / 2.5)),           # needs no op
    (stripped(NESTED), 3, None),
    (SAMPLED, 3, 100.0 * (1 - 0.9 / 2.5)),          # the probed nests'
    (((),), 3, None)])                               # no section at all
def test_offcpu_share(threads, acked, want):
    got = spec.metric_reader("host.offcpu_share").read(
        ctx_of(threads, acked))
    assert got == want if want is None else got == pytest.approx(want)


@pytest.mark.parametrize("threads,want", [
    (NESTED, {"msgr": 0.15 / 0.9, "crc": 0.05 / 0.9, "store": 0.2 / 0.9,
              "batcher": 0.3 / 0.9, "pg": 0.1 / 0.9}),
    (CLIPPED, {"msgr": 0.05 / 0.3, "crc": 0.05 / 0.3, "store": 0.2 / 0.3,
               "batcher": 0.0, "pg": 0.0}),
    (SAMPLED, {"msgr": 0.15 / 0.9, "crc": 0.05 / 0.9, "store": 0.2 / 0.9,
               "batcher": 0.3 / 0.9, "pg": 0.1 / 0.9}),
    (stripped(NESTED), dict.fromkeys(cpu.LAYERS))])
def test_layer_cpu_shares(threads, want):
    ctx = ctx_of(threads, acked=0)                  # needs no op
    for layer, share in want.items():
        got = spec.metric_reader(f"{layer}.cpu_share").read(ctx)
        assert got == share if share is None else \
            got == pytest.approx(100.0 * share), layer


def test_self_cpu_is_own_less_direct_children_and_sums_to_the_whole():
    red = cpu.reduce(plain(*NESTED))
    own = {n: round(r["self_cpu_s"], 9) for n, r in red["names"].items()}
    assert own == {"reactor.io": 0.1, "msgr.recv": 0.1,
                   "msgr.dispatch": 0.05, "pg.do_op": 0.1,
                   "crc.host": 0.05, "batcher.dispatch": 0.2,
                   "dispatch.h2d": 0.1, "store.read": 0.2}
    assert sum(own.values()) == pytest.approx(red["cpu_s"])
    assert red["busy_s"] == red["probed_s"] == pytest.approx(2.5)
    assert [(t["d"], t["cpu_s"]) for t in red["threads"]] == \
        [("osd.0", pytest.approx(0.4)), ("", pytest.approx(0.5))]


def test_an_unprobed_nest_counts_its_wall_and_no_cpu():
    red = cpu.reduce(plain(*SAMPLED))
    assert red["busy_s"] == pytest.approx(5.0)
    assert red["probed_s"] == pytest.approx(2.5)
    assert red["cpu_s"] == pytest.approx(0.9)
    assert cpu.all_cpu_s(red) == pytest.approx(1.8)
    row = red["names"]["crc.host"]
    assert (row["count"], row["probed"]) == (2, 1)
    assert row["cpu_s"] == row["self_cpu_s"] == pytest.approx(0.05)
    assert red["threads"][-1]["probed_s"] == 0.0


def test_a_reduction_narrowed_to_a_stretch_counts_it_pro_rata():
    """What the printer does inside the largest ack gap."""
    red = cpu.reduce(plain(*NESTED), (2.5e9, 2.6e9))
    assert red["names"]["pg.do_op"]["cpu_s"] == pytest.approx(0.15 / 2)
    assert red["names"]["crc.host"]["cpu_s"] == pytest.approx(0.05)
    assert red["cpu_s"] == pytest.approx(0.4 / 10)
    assert cpu.reduce(plain(*NESTED), (7e9, 8e9)) is None


def test_a_new_trace_is_never_read_as_the_last_one():
    first = ctx_of(NESTED)
    assert spec.metric_reader("host.cpu_ms_per_op").read(first) == \
        pytest.approx(300.0)
    first["spans"] = plain(*CLIPPED)
    assert spec.metric_reader("host.cpu_ms_per_op").read(first) == \
        pytest.approx(100.0)


@pytest.mark.parametrize("metric", METRICS)
def test_the_metric_is_declared_for_every_cell(metric):
    per_layer = spec.benchmark()["per_layer"]
    names = [m["name"] for m in per_layer]
    after = names.index("decode.solves_per_request") + 1
    assert names[after:after + len(METRICS)] == list(METRICS)
    row = per_layer[names.index(metric)]
    reader = spec.metric_reader(metric)
    assert (reader.SOURCE, reader.LAYER, reader.MOVES) == \
        (row["source"], row["layer"], row["moves"]) == \
        ("program_span", reader.LAYER, "throughput")
    assert "workloads" not in row and row["better"] == "lower"
    self_share = metric.replace(".cpu_share", ".self_share")
    if self_share != metric:                    # beside its wall twin
        twin = per_layer[names.index(self_share)]
        assert twin["layer"] == row["layer"]
    for cell in spec.benchmark()["workloads"]:
        assert metric in [m["name"] for m in
                          spec.Cell(cell["name"]).per_layer()]
