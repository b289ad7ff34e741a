"""The process's cache of solved recovery rows (ops/engine.py
RecoveryRowsCache, ISSUE 36) and the two bounds of the backend's LRU of
chains (ops/jax_engine.py ChainLRU): a (code, have-set, erased-set) is
solved once a process, not once a PG's codec; a pool's whole have-set
space stays resident; bindings no longer share the bound of the static
programs.
"""
import itertools
import sys
import threading

import numpy as np
import pytest

from ceph_tpu.ec import registry as ecreg
from ceph_tpu.ops import engine
from ceph_tpu.ops.jax_engine import BoundRows, ChainLRU


@pytest.fixture
def fresh_caches(monkeypatch):
    """The module's registry of caches, empty for one test."""
    monkeypatch.setattr(engine, "_ROWS_CACHES", {})


def codec(technique="reed_sol_van", k=8, m=4, **more):
    profile = {"k": str(k), "m": str(m), "technique": technique}
    profile.update({key: str(v) for key, v in more.items()})
    return ecreg.instance().factory("tpu", profile)


def signature(n: int, k: int, lost: tuple) -> tuple:
    chosen = tuple(i for i in range(n) if i not in lost)[:k]
    return chosen, tuple(i for i in range(n) if i not in chosen)


def test_two_codecs_of_one_geometry_solve_a_signature_once(fresh_caches):
    a, b = codec().core, codec().core
    assert a is not b and a._decode_cache is b._decode_cache
    chosen, erased = signature(12, 8, (0, 3, 9, 11))
    rows_a = a._recovery_rows(chosen, erased)
    rows_b = b._recovery_rows(chosen, erased)
    assert rows_b[0] is rows_a[0] and rows_b[1] is rows_a[1]
    stats = engine.rows_cache_stats()
    assert stats == {"recovery_rows_hits": 1, "recovery_rows_misses": 1,
                     "recovery_rows_entries": 1}
    # what every codec of the code shares, none may write to
    assert not rows_a[0].flags.writeable and not rows_a[1].flags.writeable


def test_codecs_of_different_matrices_never_share_an_entry(fresh_caches):
    rsv = codec().core
    cauchy = codec("cauchy_good", 10, 4, packetsize=128).core
    small = codec(k=4, m=2).core
    caches = {id(c._decode_cache) for c in (rsv, cauchy, small)}
    assert len(caches) == 3
    for core in (rsv, cauchy, small):
        n = core.k + core.m
        core._recovery_rows(*signature(n, core.k, (1,)))
    assert engine.rows_cache_stats()["recovery_rows_misses"] == 3
    assert [len(c._decode_cache) for c in (rsv, cauchy, small)] == [1, 1, 1]
    # the same (have-set, erased-set) under another matrix is another
    # system: k=8 m=4 with w=8 against the same shape on another matrix
    other = codec("reed_sol_van", 8, 4, w=16).core
    assert other._decode_cache is not rsv._decode_cache


@pytest.mark.parametrize("k,m,cap", [(8, 4, 990), (10, 4, 2002),
                                     (4, 2, 256), (20, 10, 4096)])
def test_the_bound_follows_the_geometrys_have_sets(fresh_caches, k, m, cap):
    bits = np.zeros((m * 8, k * 8), dtype=np.uint8)
    assert engine.rows_cache_for(k, m, 8, bits).cap == cap


def test_every_have_set_of_k8m4_stays_resident(fresh_caches):
    core = codec().core
    have_sets = list(itertools.combinations(range(12), 8))
    assert len(have_sets) == 495
    for chosen in have_sets:
        core._recovery_rows(
            chosen, tuple(i for i in range(12) if i not in chosen))
    assert engine.rows_cache_stats() == {
        "recovery_rows_hits": 0, "recovery_rows_misses": 495,
        "recovery_rows_entries": 495}
    for chosen in have_sets:                 # a second pass solves none
        core._recovery_rows(
            chosen, tuple(i for i in range(12) if i not in chosen))
    assert engine.rows_cache_stats()["recovery_rows_misses"] == 495
    assert engine.rows_cache_stats()["recovery_rows_hits"] == 495


def test_the_bound_evicts_past_its_size_oldest_first():
    cache = engine.RecoveryRowsCache(3)
    solved = []

    def solve(key):
        solved.append(key)
        return None, np.full((1, 1), key, dtype=np.uint8)
    for key in (1, 2, 3):
        cache.get_or_solve(key, solve, key)
    cache.get_or_solve(1, solve, 1)          # 1 is the newest now
    cache.get_or_solve(4, solve, 4)          # 2 goes
    assert len(cache) == 3 and 2 not in cache
    assert 1 in cache and 3 in cache and 4 in cache
    cache.get_or_solve(2, solve, 2)          # solved again; 3 goes
    assert solved == [1, 2, 3, 4, 2] and 3 not in cache
    assert (cache.hits, cache.misses) == (1, 5)


def test_concurrent_first_use_solves_once_or_twice_and_agrees(
        fresh_caches):
    cores = [codec().core for _ in range(2)]
    chosen, erased = signature(12, 8, (2, 5, 8, 10))
    start = threading.Barrier(2)
    got = [None, None]

    def first_use(i):
        start.wait(10)
        got[i] = cores[i]._recovery_rows(chosen, erased)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=first_use, args=(i,))
                   for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(interval)
    stats = engine.rows_cache_stats()
    assert stats["recovery_rows_misses"] in (1, 2)
    assert stats["recovery_rows_hits"] == 2 - stats["recovery_rows_misses"]
    assert stats["recovery_rows_entries"] == 1
    # both callers hold the rows the cache kept, and they are the code's
    assert got[0][0] is got[1][0] and got[0][1] is got[1][1]
    fresh = codec().core._solve_recovery_rows(chosen, erased)
    assert np.array_equal(got[0][0], fresh[0])
    assert np.array_equal(got[0][1], fresh[1])


def test_many_threads_over_many_signatures_lose_no_entry(fresh_caches):
    """More workers than cores over 60 signatures, each asked for by
    every worker: afterwards every signature is resident, and every
    lookup was a hit or a miss."""
    cores = [codec().core for _ in range(12)]
    have_sets = list(itertools.combinations(range(12), 8))[::8][:60]
    errors = []

    def work(core):
        try:
            for chosen in have_sets:
                erased = tuple(i for i in range(12) if i not in chosen)
                rows = core._recovery_rows(chosen, erased)
                if rows[0].shape != (4, 8):
                    errors.append(rows[0].shape)
        except Exception as e:               # surfaced below
            errors.append(e)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(c,)) for c in cores]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(interval)
    assert not errors
    stats = engine.rows_cache_stats()
    assert stats["recovery_rows_entries"] == 60
    assert stats["recovery_rows_hits"] + stats["recovery_rows_misses"] \
        == 12 * 60
    assert 60 <= stats["recovery_rows_misses"] <= 12 * 60


def test_the_served_packet_path_caches_its_rows_once(fresh_caches):
    """A code that has only its bit-matrix solves through the data
    inverse; that intermediate is not kept beside the rows."""
    core = codec("cauchy_good", 4, 3, packetsize=512).core
    assert core.coding_matrix is None
    chosen, erased = (0, 2, 3, 4), (1, 5, 6)
    rows = core._recovery_rows(chosen, erased)
    assert rows[0] is None and rows[1].shape == (3 * 8, 4 * 8)
    assert len(core._decode_cache) == 1
    assert ("rec", chosen, erased) in core._decode_cache
    # the entry decode_batch_device asks for lives in the same cache
    core._decode_rows(chosen, (1,))
    assert ("dec", chosen, (1,)) in core._decode_cache


# -- the LRU of chains ----------------------------------------------------------
def binding(i: int) -> BoundRows:
    return BoundRows(lambda bits, data: data, np.zeros((1, 1)), lambda k: 0)


def static(i: int) -> BoundRows:
    return BoundRows(lambda data: data, None, lambda k: 0)


def test_a_pools_bindings_outnumber_the_static_bound_and_stay():
    lru = ChainLRU(cap=4, bindings_cap=600)
    for i in range(495):
        lru.get_or_build(("gf8", i), lambda i=i: binding(i))
    assert len(lru._d) == 495                # under cap=256 alone: 256
    built = []
    for i in range(495):                     # every one a hit
        lru.get_or_build(("gf8", i), lambda: built.append(i))
    assert not built


def test_static_programs_keep_their_own_bound_beside_bindings():
    lru = ChainLRU(cap=2, bindings_cap=3)
    for i in range(3):
        lru.get_or_build(("gf8", i), lambda i=i: binding(i))
    for i in range(4):
        lru.get_or_build(("pkt", i), lambda i=i: static(i))
    keys = list(lru._d)
    assert [k for k in keys if k[0] == "pkt"] == [("pkt", 2), ("pkt", 3)]
    assert [k for k in keys if k[0] == "gf8"] == [("gf8", i)
                                                  for i in range(3)]
    lru.get_or_build(("gf8", 0), lambda: None)       # 0 is the newest
    lru.get_or_build(("gf8", 3), lambda: binding(3))  # 1 goes
    assert [k for k in lru._d if k[0] == "gf8"] == \
        [("gf8", 2), ("gf8", 0), ("gf8", 3)]
    assert lru._count == {False: 2, True: 3}


def test_the_default_bounds_hold_a_pools_signature_space():
    lru = ChainLRU()
    assert lru.cap == 256
    # as wide as the widest cache of solved rows
    assert lru.bindings_cap == 4096 >= \
        engine.rows_cache_for(10, 4, 8, np.zeros((32, 80), np.uint8)).cap
