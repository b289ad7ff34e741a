"""The reduction of the program's sections (harness/spans.py) and the
readers on top of it, runnable by hand on a CPU:

    python3 -m pytest benchmark/tests/test_spans.py -q

First on a trace written out by hand, then on the first 1.5 s of a
traced window of k8m4.write_4m on a TPU v5 lite (my chip run, PR 25),
recorded with ``spans.record`` into recorded_spans.json.gz.
"""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from harness import spans, spec, trace  # noqa: E402

RECORDED = os.path.join(HERE, "recorded_spans.json.gz")
MIB4 = 4 << 20


def by_hand():
    """Two threads, a window of [100, 1100) ns, the device busy for
    [300, 400) and [1000, 1200)."""
    t1 = [("reactor.io", 0.0, 400.0, {"d": "osd.0", "fn": "on_readable"}),
          ("msgr.recv", 50.0, 100.0, {"bytes": 64, "peer": "osd.1"}),
          ("msgr.dispatch", 160.0, 200.0, {"type": "MOSDOp"}),
          ("pg.do_op", 200.0, 100.0, {"op": "client.1:7", "pg": "1.a"}),
          ("lock.wait", 210.0, 40.0, {"site": "pg_lock", "holder": "r1"}),
          ("reactor.cb", 900.0, 400.0, {"d": "osd.0", "fn": "pump"}),
          ("PjitFunction(gf8_mxu_pallas)", 910.0, 50.0, {})]
    t2 = [(trace.WINDOW_SPAN, 100.0, 1000.0, {}),
          ("client.wait", 100.0, 1000.0, {}),
          ("batcher.dispatch", 500.0, 100.0,
           {"lane": "enc", "reqs": 2, "queue_wait_us": 300.0}),
          ("dispatch.h2d", 520.0, 30.0, {"bytes": 1000, "batch": 8}),
          ("batcher.dispatch", 700.0, 50.0,
           {"lane": "enc", "reqs": 1, "queue_wait_us": 30.0})]
    return {"lines": [[e for e in t1 if spans.is_section(e[0])],
                      [e for e in t2 if spans.is_section(e[0])
                       or e[0] == trace.WINDOW_SPAN]],
            "device_ops": [(300.0, 400.0), (1000.0, 1200.0)]}


def test_only_the_programs_own_spans_are_sections():
    assert spans.is_section("msgr.recv")
    assert spans.is_section("dispatch.stage_acquire")
    for name in ("client.wait", trace.WINDOW_SPAN, "msgr", "msgr.",
                 "PjitFunction(gf8_mxu_pallas)", "Msgr.recv",
                 "np.asarray(jax.Array)", "msgr.recv.more"):
        assert not spans.is_section(name), name


def test_self_time_is_duration_less_what_direct_children_cover():
    red = spans.reduce(by_hand())
    ns = {n: (r["count"], round(r["seconds"] * 1e9),
              round(r["self_seconds"] * 1e9))
          for n, r in red["names"].items()}
    # reactor.io is cut at the window's edge (100): 300 of its 400 ns;
    # msgr.recv [50, 150) keeps 50; the children cover 50 + 200
    assert ns["reactor.io"] == (1, 300, 50)
    assert ns["msgr.recv"] == (1, 50, 50)
    # msgr.dispatch's child is pg.do_op, whose child is lock.wait
    assert ns["msgr.dispatch"] == (1, 200, 100)
    assert ns["pg.do_op"] == (1, 100, 60)
    assert ns["lock.wait"] == (1, 40, 40)
    # cut at the window's end (1100): 200 of 400 ns; JAX's own span
    # inside it is not a section and stays in its self time
    assert ns["reactor.cb"] == (1, 200, 200)
    assert ns["batcher.dispatch"] == (2, 150, 120)
    assert red["window_s"] == pytest.approx(1000e-9)


def test_thread_busy_is_the_union_per_thread_and_sums_over_threads():
    red = spans.reduce(by_hand())
    assert sorted(round(b * 1e9) for b in red["thread_busy_s"]) == \
        [150, 500]
    assert red["busy_s"] == pytest.approx(650e-9)
    assert red["thread_d"] == ["osd.0", ""]
    assert red["open"] == [[100.0, 400.0], [500.0, 600.0],
                           [700.0, 750.0], [900.0, 1100.0]]


def test_numeric_keywords_are_summed_and_the_others_left_alone():
    red = spans.reduce(by_hand())
    sums = red["names"]["batcher.dispatch"]["sums"]
    assert sums == {"reqs": 3, "queue_wait_us": 330.0}
    assert red["names"]["msgr.recv"]["sums"] == {"bytes": 64}
    assert red["names"]["lock.wait"]["sums"] == {}
    rows = spans.breakdown(by_hand(), "lock.wait", ("site", "holder"))
    assert rows == [(("pg_lock", "r1"), 1, pytest.approx(40e-9),
                     pytest.approx(40e-9))]
    rows = spans.breakdown(by_hand(), "reactor.*", ("fn",))
    assert [r[0] for r in rows] == [("on_readable",), ("pump",)]


def test_coverage_is_idle_time_with_a_section_open_over_idle_time():
    plain = by_hand()
    red = spans.reduce(plain)
    # idle: [100, 300) all covered, [400, 1000) covered for
    # 100 + 50 + 100 of 600; the device's busy time is left out
    assert spans.coverage(red, plain["device_ops"]) == \
        pytest.approx((200 + 250) / 800)
    assert spans.coverage(red, []) is None


def test_largest_ack_gap_and_a_reduction_narrowed_to_it():
    plain = by_hand()
    assert spans.largest_ack_gap(plain) is None
    plain["lines"].append([("objecter.reply", t, 5.0, {"op": "c:1"})
                           for t in (150.0, 200.0, 640.0, 1090.0, 1500.0)])
    gap = spans.largest_ack_gap(plain)      # 1500 is past the window
    assert gap == (640.0, 1090.0)
    inside = spans.reduce(plain, gap)
    assert {n: round(r["seconds"] * 1e9)
            for n, r in inside["names"].items()} == {
        "batcher.dispatch": 50, "reactor.cb": 190, "objecter.reply": 5}
    assert spans.breakdown(plain, "lock.wait", ("site",), gap) == []


def test_record_keeps_keywords_and_rebases_to_the_window(tmp_path):
    path = str(tmp_path / "rec.json.gz")
    spans.record(by_hand(), path, 0.5e-6)        # the first 500 ns
    back = spans.load_recorded(path)
    assert spans.window_of(back) == (0.0, 500.0)
    flat = {e[0]: e for evs in back["lines"] for e in evs}
    assert flat["reactor.io"][1:3] == (0.0, 300.0)
    assert flat["lock.wait"][3] == {"site": "pg_lock", "holder": "r1"}
    assert flat["batcher.dispatch"][1:3] == (400.0, 100.0)
    assert "reactor.cb" not in flat
    assert back["device_ops"] == [(200.0, 300.0)]
    red = spans.reduce(back)
    assert red["names"]["dispatch.h2d"]["sums"] == {"bytes": 1000,
                                                    "batch": 8}


def test_a_trace_without_sections_reads_as_nothing_not_as_zero():
    """What the parent of PR 25 gives: the window span and no more."""
    bare = {"lines": [[(trace.WINDOW_SPAN, 0.0, 1e9, {})]],
            "device_ops": [(10.0, 20.0)]}
    ctx = {"spans": bare, "window": [], "ops": []}
    for m in spec.benchmark()["per_layer"]:
        reader = spec.metric_reader(m["name"])
        if "spans" in open(reader.__file__).read():
            assert reader.read(ctx) is None, m["name"]
    assert spec.metric_reader("lock.wait_share").read(
        {"spans": None}) is None


# -- the recorded chip trace -------------------------------------------------
@pytest.fixture(scope="module")
def recorded():
    if not os.path.exists(RECORDED):
        pytest.skip("no recorded spans beside this file")
    return spans.load_recorded(RECORDED)


def test_recorded_chip_trace_reduces_to_what_was_read_by_hand(recorded):
    red = spans.reduce(recorded)
    assert red["window_s"] == pytest.approx(1.5)
    assert len(red["thread_busy_s"]) == 104
    assert red["busy_s"] == pytest.approx(59.3129, abs=1e-3)
    assert spans.coverage(red, recorded["device_ops"]) == \
        pytest.approx(1.0)
    wait = red["names"]["lock.wait"]
    assert wait["count"] == 1911
    assert wait["seconds"] == pytest.approx(46.0705, abs=1e-3)
    by_site = spans.breakdown(recorded, "lock.wait", ("site",))
    assert by_site[0][:2] == (("config",), 1898)
    # a section is never shorter than what its children cover
    assert all(r["self_seconds"] >= -1e-9 and
               r["self_seconds"] <= r["seconds"] + 1e-9
               for r in red["names"].values())
    assert sum(r["self_seconds"] for r in red["names"].values()) == \
        pytest.approx(red["busy_s"], rel=1e-6)


READ_ON_FIXTURE = {
    "host.span_coverage": 100.0,
    "host.busy_threads": 39.5419,
    "msgr.self_share": 6.5707,
    "pg.self_share": 0.0979,
    "store.self_share": 0.2863,
    "crc.self_share": 9.6154,
    "batcher.self_share": 1.0374,
    "lock.wait_share": 77.6736,
    "batcher.queue_wait_ms": 2.9466,
    # 8 writes of 4 MiB acked: 645,922,816 bytes in (8 MiB a CRC
    # call, its 128 4-KiB blocks padded 16 times over) and 21,495,808 out
    "link.bytes_per_user_byte": 19.8906,
    "msgr.reconnects_in_window": 0.0,
}


@pytest.mark.parametrize("name", sorted(READ_ON_FIXTURE))
def test_reader_on_the_recorded_trace(recorded, name):
    ops = [{"op": "write_full", "io_bytes": MIB4}]
    acked = [(0.0, 0.1 * i, 0, i, 0, 0, 0, MIB4, None) for i in range(8)]
    failed = [(0.0, 1.0, 0, 9, 0, 0, -110, 0, None)]
    ctx = {"spans": recorded, "window": acked + failed, "ops": ops}
    reader = spec.metric_reader(name)
    row = [m for m in spec.benchmark()["per_layer"] if m["name"] == name]
    assert row and row[0]["moves"] == reader.MOVES == "throughput"
    assert row[0]["source"] == reader.SOURCE
    assert row[0]["layer"] == reader.LAYER and "workloads" not in row[0]
    assert reader.read(ctx) == pytest.approx(READ_ON_FIXTURE[name],
                                             abs=1e-3)
