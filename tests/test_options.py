"""Config option surface tests.

The reference declares 1,676 options in one table
(src/common/options.cc); r2/r3 VERDICTs asked for >= 150 here, each
READ by real code.  These tests hold both properties: the count, and —
the part that keeps the table honest — that every declared option name
is referenced somewhere outside the table itself (a declared-but-dead
option is documentation posing as a feature).
"""
import os
import re
import subprocess
import sys
import threading
import time

import pytest

from ceph_tpu.utils.config import Config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "ceph_tpu")

# families consumed via computed names: f"debug_{subsys}"
# (utils/log.py get_subsys_level), the mclock triples
# (f"osd_mclock_scheduler_{cls}_{knob}" in osd/scheduler.py
# qos_from_conf), and the hdd/ssd-tuned variants
# (f"{base}_{medium}" in OSD._tuned)
COMPUTED_PREFIXES = ("debug_", "osd_mclock_scheduler_")
COMPUTED_SUFFIXES = ("_hdd", "_ssd")
COMPUTED_EXCEPT = ("debug_default_level",)


def _grep_sources():
    out = {}
    for root, _dirs, files in os.walk(PKG):
        for fn in files:
            if fn.endswith(".py") and fn != "config.py":
                path = os.path.join(root, fn)
                with open(path, encoding="utf-8") as fh:
                    out[path] = fh.read()
    # bench.py and tools consume options too
    with open(os.path.join(REPO, "bench.py"), encoding="utf-8") as fh:
        out["bench.py"] = fh.read()
    return out


def test_option_count_at_least_150():
    n = len(Config().schema)
    assert n >= 150, f"only {n} options declared (need >= 150)"


def test_every_option_is_consumed_outside_the_table():
    sources = _grep_sources()
    blob = "\n".join(sources.values())
    dead = []
    for name in Config().schema:
        computed = name.startswith(COMPUTED_PREFIXES) or \
            name.endswith(COMPUTED_SUFFIXES)
        if name in COMPUTED_EXCEPT or not computed:
            if name not in blob:
                dead.append(name)
    assert not dead, f"declared but never read: {dead}"


def test_option_validation_and_layering():
    c = Config()
    # enum + range validation
    with pytest.raises(ValueError):
        c.set("osd_op_queue", "bogus-queue")
    with pytest.raises(ValueError):
        c.set("compressor_zlib_level", 99)
    with pytest.raises(KeyError):
        c.set("no_such_option", 1)
    # runtime overrides layer over defaults and unset falls back
    c.set("osd_min_pg_log_entries", 123)
    assert c["osd_min_pg_log_entries"] == 123
    c.unset("osd_min_pg_log_entries")
    assert c["osd_min_pg_log_entries"] == \
        c.schema["osd_min_pg_log_entries"].default


def test_debug_subsys_levels_flow_through():
    from ceph_tpu.utils.config import default_config
    from ceph_tpu.utils.log import get_subsys_level
    conf = default_config()
    conf.set("debug_osd", 7)
    try:
        assert get_subsys_level("osd") == 7
        # -1 inherits debug_default_level
        conf.set("debug_mon", -1)
        assert get_subsys_level("mon") == \
            conf["debug_default_level"]
    finally:
        conf.unset("debug_osd")
        conf.unset("debug_mon")


# -- the read path: one lookup on a published snapshot, no lock ---------

OPT = "osd_min_pg_log_entries"


def _on_thread(fn, timeout=1.0):
    """Run ``fn`` on a thread of its own under a watchdog; its result,
    or a failure if it has not returned in ``timeout`` seconds."""
    out = []
    t = threading.Thread(target=lambda: out.append(fn()), daemon=True)
    t.start()
    t.join(timeout)
    assert not t.is_alive(), "the call did not return: it blocked"
    return out[0]


@pytest.mark.parametrize("read", [
    lambda c: c.get(OPT),
    lambda c: c[OPT],
    lambda c: c.dump()[OPT],
], ids=["get", "getitem", "dump"])
def test_a_read_returns_while_a_writer_holds_the_lock(read):
    c = Config()
    held, release = threading.Event(), threading.Event()

    def holder():
        with c._lock:
            held.set()
            release.wait(10)
    t = threading.Thread(target=holder, daemon=True)
    t.start()
    assert held.wait(5)
    try:
        assert _on_thread(lambda: read(c)) == c.schema[OPT].default
    finally:
        release.set()
        t.join(5)


def test_a_set_is_seen_by_every_thread_once_it_has_returned():
    c = Config()
    for value in (11, 12, 13):
        _on_thread(lambda: c.set(OPT, value))
        assert c.get(OPT) == value              # the next reader
        assert _on_thread(lambda: c[OPT]) == value
    c.set(OPT, 14)
    assert c.get(OPT) == 14                     # the setter itself


def test_layering_and_unset_fall_back_through_all_five_sources():
    c = Config()
    default = c.schema[OPT].default
    layers = Config.SOURCES[1:]                 # file < env < cli < runtime
    # from the top down: a lower layer is shadowed by what is set above
    c.set(OPT, 104, source="runtime")
    for i, source in reversed(list(enumerate(layers[:-1], 101))):
        c.set(OPT, i, source=source)
        assert c.get(OPT) == c[OPT] == c.dump()[OPT] == 104
    assert c.is_overridden(OPT)
    # unset from the top: each falls back to the next layer below it
    for want, source in zip((103, 102, 101, default), reversed(layers)):
        c.unset(OPT, source=source)
        assert c.get(OPT) == c[OPT] == c.dump()[OPT] == want
        assert c.diff() == ({OPT: want} if want != default else {})
    assert not c.is_overridden(OPT)
    assert set(c.dump()) == set(c.schema)
    with pytest.raises(ValueError):
        c.set(OPT, 1, source="no-such-source")


def test_environment_and_constructor_overrides_are_in_the_first_reads(
        monkeypatch):
    monkeypatch.setenv("CEPH_TPU_" + OPT.upper(), "77")
    monkeypatch.setenv("CEPH_TPU_OSD_MAX_BACKFILLS", "5")
    c = Config({"osd_max_backfills": 6})
    assert c[OPT] == 77 and c["osd_max_backfills"] == 6     # env < cli
    c.unset("osd_max_backfills", source="cli")
    assert c["osd_max_backfills"] == 5
    assert c.diff() == {OPT: 77, "osd_max_backfills": 5}


def test_an_observer_fires_once_per_effective_change_after_the_swap():
    c = Config()
    calls = []

    def lock_is_free():
        got = c._lock.acquire(False)
        if got:
            c._lock.release()
        return got

    # what the observer reads is already the new value, and the
    # writers' lock is free: another thread's set would not deadlock
    c.add_observer(OPT, lambda name, new: calls.append(
        (name, new, c.get(name), _on_thread(lock_is_free))))
    c.set(OPT, 21)
    c.set(OPT, 21)                              # no-op: not called
    c.set(OPT, 15, source="file")               # shadowed: not called
    c.unset(OPT)                                # falls to the file layer
    c.unset(OPT)                                # nothing to drop
    assert calls == [(OPT, 21, 21, True), (OPT, 15, 15, True)]


@pytest.mark.parametrize("call", [
    lambda c: c.get("no_such_option"),
    lambda c: c["no_such_option"],
    lambda c: c.set("no_such_option", 1),
    lambda c: c.unset("no_such_option"),
    lambda c: c.add_observer("no_such_option", print),
    lambda c: c.is_overridden("no_such_option"),
    lambda c: Config({"no_such_option": 1}),
], ids=["get", "getitem", "set", "unset", "add_observer", "is_overridden",
        "constructor"])
def test_an_unknown_name_raises_key_error(call):
    with pytest.raises(KeyError, match="unknown option 'no_such_option'"):
        call(Config())


@pytest.mark.parametrize("step, rises", [
    (lambda c: c.set(OPT, 31), 1),
    (lambda c: c.set(OPT, 19), 0),                          # no-op
    (lambda c: c.set(OPT, 31, source="file"), 0),           # shadowed
    (lambda c: c.unset(OPT, source="cli"), 1),
    (lambda c: c.unset(OPT), 0),                            # not set
    (lambda c: (c.get(OPT), c.dump(), c.diff()), 0),        # reads
    (lambda c: c.set(OPT, "not a number"), 0),              # refused
], ids=["set", "noop_set", "shadowed_set", "unset", "noop_unset", "reads",
        "refused_set"])
def test_generation_rises_by_one_per_effective_publish(step, rises):
    c = Config({OPT: 19})
    before, snapshot = c.generation, c._merged
    frozen = dict(snapshot)
    try:
        step(c)
    except ValueError:
        pass
    assert c.generation - before == rises
    # a published mapping is never mutated: a change is a new mapping
    assert snapshot == frozen
    assert (c._merged is snapshot) == (rises == 0)


def test_central_config_sets_reverts_and_skips_unknown_names():
    from ceph_tpu.utils.config import apply_cluster_config_overrides
    c = Config()
    default = c.schema[OPT].default
    seen = []
    c.add_observer(OPT, lambda name, new: seen.append(new))
    applied = apply_cluster_config_overrides(
        c, {OPT: "41", "no_such_option": "1", "osd_max_backfills": "x"}, {})
    assert applied == {OPT: "41"} and c[OPT] == 41
    generation = c.generation
    applied = apply_cluster_config_overrides(c, {OPT: "41"}, applied)
    assert c.generation == generation           # the same map again
    applied = apply_cluster_config_overrides(c, {}, applied)
    assert applied == {} and c[OPT] == default
    assert seen == [41, default]


def test_concurrent_writers_lose_no_update_and_readers_never_go_back():
    """More writers and readers than cores, a short switch interval,
    and a publish that dawdles between building its mapping and
    storing it: each writer walks an option of its own upwards; a
    snapshot built from a stale copy would drop another writer's key,
    and a reader would see a value fall."""
    names = ["osd_min_pg_log_entries", "osd_max_pg_log_entries",
             "osd_max_backfills", "osd_recovery_max_active",
             "osd_op_num_shards", "osd_heartbeat_min_peers",
             "objecter_inflight_ops", "trace_keep_spans"]
    c = Config()
    steps, went_back, stop = 50, [], threading.Event()
    publish = c._publish
    c._publish = lambda merged: (time.sleep(1e-4), publish(merged))
    base = {n: int(c[n]) for n in names}
    room = {n: c.schema[n].max for n in names}
    assert all(r is None or r >= base[n] + steps for n, r in room.items()), \
        room
    before = c.generation

    def writer(name):
        for i in range(1, steps + 1):
            c.set(name, base[name] + i)

    def reader():
        last = dict(base)
        while not stop.is_set():
            for n in names:
                v = c[n]
                if v < last[n]:
                    went_back.append((n, last[n], v))
                last[n] = v

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        readers = [threading.Thread(target=reader, daemon=True)
                   for _ in range(8)]
        writers = [threading.Thread(target=writer, args=(n,), daemon=True)
                   for n in names]
        for t in readers + writers:
            t.start()
        for t in writers:
            t.join(60)
        stop.set()
        for t in readers:
            t.join(10)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in readers + writers)
    assert went_back == []
    assert {n: c[n] for n in names} == {n: base[n] + steps for n in names}
    assert c.dump() == {**Config().dump(),
                        **{n: base[n] + steps for n in names}}
    assert c.generation - before == steps * len(names)
