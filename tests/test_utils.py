"""Runtime-primitive tests: config layering/observers, perf counters,
admin socket round trip, op tracker (reference analogs:
src/test/common/test_config.cc, perf_counters tests,
test_admin_socket.cc)."""
import os
import tempfile
import threading

import pytest

from ceph_tpu.utils import (AdminSocket, Config, OpTracker, PerfCounters,
                            PerfCountersCollection, TimeScope,
                            admin_command)


class TestConfig:
    def test_defaults(self):
        conf = Config()
        assert conf.get("osd_op_num_shards") == 5
        assert conf["ms_crc_data"] is True

    def test_unknown_key(self):
        conf = Config()
        with pytest.raises(KeyError):
            conf.get("no_such_option")
        with pytest.raises(KeyError):
            conf.set("no_such_option", 1)

    def test_precedence(self):
        conf = Config()
        conf.set("osd_op_num_shards", 7, source="file")
        assert conf.get("osd_op_num_shards") == 7
        conf.set("osd_op_num_shards", 9, source="runtime")
        assert conf.get("osd_op_num_shards") == 9
        # lower-precedence source does not override
        conf.set("osd_op_num_shards", 3, source="file")
        assert conf.get("osd_op_num_shards") == 9

    def test_validation(self):
        conf = Config()
        with pytest.raises(ValueError):
            conf.set("osd_op_num_shards", 0)      # min=1
        with pytest.raises(ValueError):
            conf.set("osd_op_num_shards", "abc")
        conf.set("ms_crc_data", "false")
        assert conf.get("ms_crc_data") is False

    def test_observer(self):
        conf = Config()
        seen = []
        conf.add_observer("osd_recovery_max_active",
                          lambda k, v: seen.append((k, v)))
        conf.set("osd_recovery_max_active", 8)
        conf.set("osd_recovery_max_active", 8)  # no-op: unchanged
        assert seen == [("osd_recovery_max_active", 8)]

    def test_env_source(self, monkeypatch):
        monkeypatch.setenv("CEPH_TPU_OSD_MAX_BACKFILLS", "5")
        conf = Config()
        assert conf.get("osd_max_backfills") == 5

    def test_diff(self):
        conf = Config()
        conf.set("osd_max_backfills", 4)
        assert conf.diff() == {"osd_max_backfills": 4}


class TestPerfCounters:
    def test_counter_and_avg(self):
        c = PerfCounters("osd")
        c.add("ops")
        c.add_time_avg("op_lat")
        for i in range(10):
            c.inc("ops")
            c.tinc("op_lat", 0.5)
        assert c.get("ops") == 10
        assert c.avg("op_lat") == pytest.approx(0.5)
        dump = c.dump()
        assert dump["ops"] == 10
        assert dump["op_lat"] == {"avgcount": 10, "sum": pytest.approx(5.0)}

    def test_histogram(self):
        c = PerfCounters("osd")
        c.add_histogram("sizes", [10, 100, 1000])
        for v in (5, 50, 500, 5000, 7):
            c.hinc("sizes", v)
        assert c.dump()["sizes"]["buckets"] == [2, 1, 1, 1]

    def test_collection(self):
        coll = PerfCountersCollection()
        a = coll.create("osd")
        a.add("ops")
        a.inc("ops", 3)
        assert coll.perf_dump()["osd"]["ops"] == 3

    def test_time_scope(self):
        c = PerfCounters("x")
        c.add_time_avg("lat")
        with TimeScope(c, "lat"):
            pass
        assert c.dump()["lat"]["avgcount"] == 1


class TestAdminSocket:
    def test_round_trip(self):
        with tempfile.TemporaryDirectory() as d:
            path = os.path.join(d, "asok")
            sock = AdminSocket(path)
            coll = PerfCountersCollection()
            pc = coll.create("osd")
            pc.add("ops")
            pc.inc("ops", 42)
            sock.register("perf dump", lambda cmd: coll.perf_dump())
            sock.register("echo", lambda cmd: cmd.get("payload"))
            sock.start()
            try:
                out = admin_command(path, "perf dump")
                assert out["osd"]["ops"] == 42
                assert admin_command(path, "echo", payload=[1, 2]) == [1, 2]
                with pytest.raises(RuntimeError, match="unknown command"):
                    admin_command(path, "nope")
                assert "perf dump" in admin_command(path, "help")
            finally:
                sock.stop()


class TestOpTracker:
    def test_lifecycle(self):
        t = OpTracker(history_size=2)
        op = t.create("osd_op(write)")
        op.mark_event("queued")
        op.mark_event("commit")
        assert len(t.dump_ops_in_flight()) == 1
        op.finish()
        assert t.dump_ops_in_flight() == []
        hist = t.dump_historic_ops()
        assert len(hist) == 1
        events = [e["event"] for e in hist[0]["events"]]
        assert events == ["initiated", "queued", "commit", "done"]

    def test_slow_ops(self):
        t = OpTracker(slow_op_warn_threshold=0.0)
        t.create("slowpoke")
        assert len(t.slow_ops()) == 1


def test_native_library_is_keyed_by_source_and_host_cpu(monkeypatch):
    """The -march=native helpers build into a name that carries the
    source text and this host's CPU, so a binary carried in from
    another machine (or built from other source) has another name and
    is never loaded; a checkout without one builds it."""
    from ceph_tpu.utils import nativebuild

    here = nativebuild.lib_path("crc32c.cc", "libceph_tpu_crc32c")
    assert os.path.dirname(here).endswith(os.path.join("native", "build"))
    monkeypatch.setattr(nativebuild, "_host_cpu",
                        lambda: "x86_64\nmodel name: some other host")
    elsewhere = nativebuild.lib_path("crc32c.cc", "libceph_tpu_crc32c")
    assert elsewhere != here
    monkeypatch.undo()
    assert nativebuild.lib_path("gf_native.cc", "libceph_tpu_crc32c") \
        != here                          # other source, other name
    lib = nativebuild.load("crc32c.cc", "libceph_tpu_crc32c")
    if lib is not None:                  # a compiler is present
        assert os.path.exists(here)
