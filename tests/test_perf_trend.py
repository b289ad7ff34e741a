"""tools/perf_trend.py regression-gate tests (PR 6 satellite).

Synthetic BENCH_r0N.json-style history fixtures drive the three gate
verdicts: clean pass, per-stage regression, and the r05 signature —
device_encode_fraction collapsing to ~0 while the device demonstrably
wins — which must fail with a routing-collapse diagnosis.
"""
import json
import subprocess
import sys

import pytest

sys.path.insert(0, __file__.rsplit("/tests/", 1)[0])
from tools import perf_trend  # noqa: E402


def _hist_round(tmp_path, n, records):
    tail = "\n".join(json.dumps(r) for r in records)
    p = tmp_path / f"BENCH_r{n:02d}.json"
    p.write_text(json.dumps(
        {"n": n, "cmd": "python bench.py", "rc": 0, "tail": tail,
         "parsed": records[-1] if records else None}))
    return str(p)


def _attribution(stages, frac, expect=True):
    return {"metric": "cluster k8m4 write per-stage time attribution"
                      " (wall split ...)",
            "value": round(sum(stages.values()), 3), "unit": "s",
            "vs_baseline": 1.0, "stages": stages,
            "device_encode_fraction": frac, "expect_device": expect,
            "routing": {"device_reqs": int(frac * 100),
                        "cpu_twin_reqs": 100 - int(frac * 100)}}


def _cluster(vs):
    return {"metric": "cluster write MB/s (13-OSD vstart, pool "
                      "plugin=tpu k=8 m=4, ...)",
            "value": 25.0 * vs, "unit": "MB/s", "vs_baseline": vs}


def _headline(vs):
    return {"metric": "EC encode GiB/s at the codec boundary "
                      "(plugin=tpu ...)",
            "value": 30.0, "unit": "GiB/s", "vs_baseline": vs}


@pytest.fixture
def history(tmp_path):
    good = _attribution({"queue_wait": 1.0, "encode": 2.0,
                         "commit": 3.0}, 0.95)
    return [
        _hist_round(tmp_path, 1, [_headline(15.0)]),
        _hist_round(tmp_path, 2,
                    [_headline(17.0), _cluster(1.0), good]),
    ]


def _run_cli(fresh_path, history):
    return subprocess.run(
        [sys.executable, "tools/perf_trend.py",
         "--fresh", str(fresh_path), "--history", *history],
        capture_output=True, text=True)


def test_fresh_run_matching_history_passes(tmp_path, history):
    fresh = tmp_path / "fresh.json"
    fresh.write_text("\n".join(json.dumps(r) for r in (
        _headline(17.5), _cluster(1.05),
        _attribution({"queue_wait": 1.1, "encode": 2.1,
                      "commit": 2.9}, 0.97))))
    r = _run_cli(fresh, history)
    assert r.returncode == 0, (r.stdout, r.stderr)
    assert "perf_trend ok" in r.stdout


def test_per_stage_regression_fails(tmp_path, history):
    # queue_wait balloons from 1/6 to ~2/3 of the wall
    fresh = tmp_path / "fresh.json"
    fresh.write_text(json.dumps(
        _attribution({"queue_wait": 12.0, "encode": 2.0,
                      "commit": 3.0}, 0.95)))
    r = _run_cli(fresh, history)
    assert r.returncode == 1, (r.stdout, r.stderr)
    assert "stage-regression" in r.stdout
    assert "queue_wait" in r.stdout


def test_routing_collapse_fails_with_diagnosis(tmp_path, history):
    # the r05 replay: throughput collapses alongside a device
    # fraction of ~0 even though calibration expected the device
    fresh = tmp_path / "fresh.json"
    fresh.write_text("\n".join(json.dumps(r) for r in (
        _headline(17.5), _cluster(0.55),
        _attribution({"queue_wait": 1.0, "encode": 6.0,
                      "commit": 3.0}, 0.0, expect=True))))
    r = _run_cli(fresh, history)
    assert r.returncode == 1, (r.stdout, r.stderr)
    assert "routing-collapse" in r.stdout
    assert "misrouted to the CPU twin" in r.stdout
    assert "throughput-regression" in r.stdout


def test_collapse_detected_via_headline_without_pin(history):
    # no calibration pin recorded (expect_device=None): the fresh
    # codec-boundary headline proving the device fast is enough
    att = _attribution({"queue_wait": 1.0, "encode": 2.0,
                        "commit": 3.0}, 0.0, expect=None)
    findings = perf_trend.check(
        att, perf_trend.load_history(history),
        fresh_headline_ratio=17.5)
    assert [f["check"] for f in findings] == ["routing-collapse"]
    # ... but a CPU-only box (device never proven) must not trip
    assert perf_trend.check(
        att, perf_trend.load_history(history),
        fresh_headline_ratio=0.9) == []


def test_twin_expected_run_passes(history):
    # calibration decided the twin wins (expect_device=False): a low
    # device fraction is CORRECT routing, not a collapse
    att = _attribution({"queue_wait": 1.0, "encode": 2.0,
                        "commit": 3.0}, 0.02, expect=False)
    assert perf_trend.check(
        att, perf_trend.load_history(history)) == []


def test_no_data_exits_2(tmp_path, history):
    fresh = tmp_path / "empty.json"
    fresh.write_text("no metrics here\n")
    r = _run_cli(fresh, history)
    assert r.returncode == 2


def _scaling(mbps16, clients=None):
    cl = clients or {"1": 60.0, "4": 55.0, "16": mbps16, "64": 30.0}
    return {"metric": "cluster write scaling 1/4/16/64 concurrent "
                      "clients (classic vs crimson, 3-OSD k=2 m=1; "
                      "value = crimson 16-client MB/s)",
            "value": cl["16"], "unit": "MB/s", "vs_baseline": 2.5,
            "classic": {"clients": {"16": cl["16"] / 2.5}},
            "crimson": {"clients": cl}}


def test_scaling_gate_skips_without_history(history):
    """Rounds predating the cluster_scaling ladder must not fail the
    gate (ISSUE 8 self-skip contract)."""
    findings = perf_trend.check(
        None, perf_trend.load_history(history),
        fresh_scaling={"16": 1.0})
    assert not [f for f in findings
                if f["check"] == "scaling-regression"]


def test_scaling_gate_fails_on_16_client_regression(tmp_path,
                                                    history):
    hist = history + [_hist_round(tmp_path, 3, [_scaling(42.0)])]
    findings = perf_trend.check(
        None, perf_trend.load_history(hist),
        fresh_scaling={"16": 20.0})         # < 0.8 x 42.0
    assert [f for f in findings
            if f["check"] == "scaling-regression"]
    # at tolerance, it passes
    findings = perf_trend.check(
        None, perf_trend.load_history(hist),
        fresh_scaling={"16": 40.0})         # >= 0.8 x 42.0
    assert not findings


def test_scaling_gate_runs_from_cli_fresh_records(tmp_path, history):
    hist = history + [_hist_round(tmp_path, 3, [_scaling(42.0)])]
    good = _attribution({"queue_wait": 1.0, "encode": 2.0,
                         "commit": 3.0}, 0.95)
    fresh = tmp_path / "fresh.json"
    fresh.write_text("\n".join(json.dumps(r) for r in (
        _headline(17.0), _cluster(1.0), good, _scaling(18.0))))
    r = _run_cli(fresh, hist)
    assert r.returncode == 1, (r.stdout, r.stderr)
    assert "scaling-regression" in r.stdout


# ------------------------------- ISSUE 13: ladder + open-loop load gates
def _load_rec(read_p99=12.0, write_p99=20.0, errors=0, burn=None):
    return {"metric": "open-loop load attribution (200 clients x 2 "
                      "RGW gateways, mixed GET/PUT/DELETE + "
                      "multipart, zipf hot keys, poisson open-loop "
                      "arrivals against absolute deadlines; value = "
                      "client_read p99 ms)",
            "value": read_p99, "unit": "ms", "vs_baseline": 0.01,
            "clients": 200, "gateways": 2, "errors": errors,
            "latency_ms": {
                "client_read": {"ops": 90, "p50_ms": 4.0,
                                "p95_ms": 9.0, "p99_ms": read_p99,
                                "target_ms": 30000.0},
                "client_write": {"ops": 110, "p50_ms": 7.0,
                                 "p95_ms": 14.0, "p99_ms": write_p99,
                                 "target_ms": 30000.0}},
            "contention": {"victim_osd": 2, "recovery_burn": 1.4,
                           "client_burn": burn or
                           {"client_read": 0.0, "client_write": 0.0}}}


def test_load_gate_skips_without_history(history):
    """Rounds predating the load harness carry no load attribution:
    the p99 half must self-skip (ISSUE 13 satellite)."""
    findings = perf_trend.check(
        None, perf_trend.load_history(history),
        fresh_load=_load_rec(read_p99=5000.0))
    assert not [f for f in findings
                if f["check"] == "load-p99-regression"], findings


def test_load_gate_fails_on_p99_regression(tmp_path, history):
    hist = history + [_hist_round(tmp_path, 3, [_load_rec()])]
    rounds = perf_trend.load_history(hist)
    # client_read p99 blows 1.5x + 1 ms past the last load round
    findings = perf_trend.check(
        None, rounds, fresh_load=_load_rec(read_p99=40.0))
    hits = [f for f in findings if f["check"] == "load-p99-regression"]
    assert len(hits) == 1 and "client_read" in hits[0]["message"]
    # within tolerance (<= 1.5 x 12 ms) it passes
    assert not perf_trend.check(
        None, rounds, fresh_load=_load_rec(read_p99=17.0))


def test_load_gate_errors_and_burn_need_no_history(history):
    """The zero-error / zero-client-burn promises are absolute — they
    re-assert even when no history round carries a load record."""
    findings = perf_trend.check(
        None, perf_trend.load_history(history),
        fresh_load=_load_rec(errors=3,
                             burn={"client_read": 0.5,
                                   "client_write": 0.0}))
    checks = [f["check"] for f in findings]
    assert "load-client-errors" in checks
    assert "load-qos-regression" in checks
    qos = [f for f in findings if f["check"] == "load-qos-regression"]
    assert len(qos) == 1 and "client_read" in qos[0]["message"]


def test_ladder_gate_crimson_must_win_every_rung(history):
    """The tentpole's acceptance: crimson >= classic at EVERY rung of
    the concurrency ladder, asserted within one fresh run."""
    rounds = perf_trend.load_history(history)
    losing = {"classic": {"1": 40.0, "4": 45.0, "16": 50.0,
                          "64": 38.2},
              "crimson": {"1": 60.0, "4": 55.0, "16": 52.0,
                          "64": 29.7}}
    findings = perf_trend.check(None, rounds, fresh_ladder=losing)
    hits = [f for f in findings
            if f["check"] == "crimson-ladder-regression"]
    assert len(hits) == 1 and "64-client" in hits[0]["message"]
    winning = {"classic": {"1": 40.0, "4": 45.0, "16": 50.0,
                           "64": 38.2},
               "crimson": {"1": 60.0, "4": 55.0, "16": 52.0,
                           "64": 41.0}}
    assert not perf_trend.check(None, rounds, fresh_ladder=winning)


def test_load_and_ladder_gates_run_from_cli(tmp_path, history):
    hist = history + [_hist_round(tmp_path, 3, [_load_rec()])]
    good = _attribution({"queue_wait": 1.0, "encode": 2.0,
                         "commit": 3.0}, 0.95)
    fresh = tmp_path / "fresh.json"
    fresh.write_text("\n".join(json.dumps(r) for r in (
        _headline(17.0), _cluster(1.0), good,
        _load_rec(read_p99=40.0))))
    r = _run_cli(fresh, hist)
    assert r.returncode == 1, (r.stdout, r.stderr)
    assert "load-p99-regression" in r.stdout


# ---------------------------------------- ISSUE 10: device-path gates
def _dwf(frac, p99=None, groups=120):
    return {"groups": groups, "wall_s": 1.0,
            "phase_seconds": {"h2d_done": 0.3, "compute_done": 0.5,
                              "d2h_done": 0.2},
            "shares": {"h2d_done": 0.3, "compute_done": 0.5,
                       "d2h_done": 0.2},
            "p99_s": p99 or {"h2d_done": 0.002,
                             "compute_done": 0.005},
            "sum_of_shares": 1.0, "top_phase": "compute_done",
            "pipeline_overlap_frac": frac,
            "bounding_phase": "h2d_done",
            "bubble_s": {"h2d_done": 0.05}, "devices": [0]}


def _att_with_dwf(frac, dwf, expect=True):
    att = _attribution({"queue_wait": 1.0, "encode": 2.0,
                        "commit": 3.0}, frac, expect=expect)
    att["device_waterfall"] = dwf
    return att


def test_overlap_gate_skips_without_device_history(history):
    """History rounds predating the device ledger carry no
    device_waterfall; the overlap and device-p99 gates self-skip."""
    findings = perf_trend.check(
        _att_with_dwf(0.95, _dwf(0.0)),
        perf_trend.load_history(history))
    assert not [f for f in findings
                if f["check"] in ("overlap-collapse",
                                  "device-phase-p99-regression")]


def test_overlap_gate_fails_on_collapse(tmp_path, history):
    hist = history + [_hist_round(
        tmp_path, 3, [_att_with_dwf(0.95, _dwf(0.6))])]
    rounds = perf_trend.load_history(hist)
    findings = perf_trend.check(
        _att_with_dwf(0.95, _dwf(0.05)), rounds)
    assert [f for f in findings if f["check"] == "overlap-collapse"]
    assert "h2d no longer hides under compute" in \
        [f for f in findings
         if f["check"] == "overlap-collapse"][0]["message"]
    # at tolerance (>= 0.5 x 0.6) it passes
    assert not [f for f in
                perf_trend.check(_att_with_dwf(0.95, _dwf(0.35)),
                                 rounds)
                if f["check"] == "overlap-collapse"]


def test_overlap_gate_cpu_only_box_does_not_trip(tmp_path, history):
    """The non-trip case: a CPU-only box legitimately reports overlap
    0 — calibration expected the twin and zero requests routed to the
    device — and must NOT fail the floor even though history (from a
    TPU box) carries a healthy overlap."""
    hist = history + [_hist_round(
        tmp_path, 3, [_att_with_dwf(0.95, _dwf(0.6))])]
    att = _att_with_dwf(0.0, _dwf(0.0), expect=False)
    assert att["routing"]["device_reqs"] == 0
    findings = perf_trend.check(att, perf_trend.load_history(hist))
    assert not [f for f in findings
                if f["check"] == "overlap-collapse"], findings


def test_device_phase_p99_gate(tmp_path, history):
    hist = history + [_hist_round(
        tmp_path, 3, [_att_with_dwf(0.95, _dwf(0.6))])]
    rounds = perf_trend.load_history(hist)
    # h2d_done p99 blows 5x past history (and > 1 ms absolute)
    bad = _dwf(0.6, p99={"h2d_done": 0.010, "compute_done": 0.005})
    findings = perf_trend.check(_att_with_dwf(0.95, bad), rounds)
    hits = [f for f in findings
            if f["check"] == "device-phase-p99-regression"]
    assert len(hits) == 1 and "h2d_done" in hits[0]["message"]
    # a fresh run that routed no groups to the device self-skips
    empty = _dwf(0.0, p99={"h2d_done": 0.010}, groups=0)
    assert not [f for f in
                perf_trend.check(
                    _att_with_dwf(0.0, empty, expect=False), rounds)
                if f["check"] == "device-phase-p99-regression"]


def test_overlap_gate_runs_from_cli(tmp_path, history):
    hist = history + [_hist_round(
        tmp_path, 3, [_att_with_dwf(0.95, _dwf(0.6))])]
    fresh = tmp_path / "fresh.json"
    fresh.write_text("\n".join(json.dumps(r) for r in (
        _headline(17.0), _cluster(1.0),
        _att_with_dwf(0.95, _dwf(0.05)))))
    r = _run_cli(fresh, hist)
    assert r.returncode == 1, (r.stdout, r.stderr)
    assert "overlap-collapse" in r.stdout
    # --overlap-tol 0 disables the floor
    r = subprocess.run(
        [sys.executable, "tools/perf_trend.py",
         "--fresh", str(fresh), "--history", *hist,
         "--overlap-tol", "0"],
        capture_output=True, text=True)
    assert r.returncode == 0, (r.stdout, r.stderr)


# ------------------------------------- ISSUE 16: store-phase p99 gate
def _swf(p99=None, txns=400):
    return {"txns": txns, "wall_s": 2.0,
            "phase_seconds": {"journal_fsync": 0.8,
                              "data_write": 0.9, "kv_commit": 0.3},
            "shares": {"journal_fsync": 0.4, "data_write": 0.45,
                       "kv_commit": 0.15},
            "p99_s": p99 or {"journal_fsync": 0.004,
                             "data_write": 0.005,
                             "kv_commit": 0.001},
            "sum_of_shares": 1.0, "top_phase": "data_write",
            "stalls": 0, "io": {"bytes_written": 1 << 26}}


def _att_with_swf(swf):
    att = _attribution({"queue_wait": 1.0, "encode": 2.0,
                        "commit": 3.0}, 0.95)
    att["store_waterfall"] = swf
    return att


def test_store_phase_gate_skips_without_store_history(history):
    """History rounds predating the store ledger carry no
    store_waterfall block; the store-phase gate self-skips — a fresh
    run with arbitrarily slow phases must not fail against rounds
    that never measured them."""
    bad = _swf(p99={"journal_fsync": 5.0, "data_write": 9.0})
    findings = perf_trend.check(_att_with_swf(bad),
                                perf_trend.load_history(history))
    assert not [f for f in findings
                if f["check"] == "store-phase-p99-regression"]


def test_store_phase_p99_gate(tmp_path, history):
    hist = history + [_hist_round(
        tmp_path, 3, [_att_with_swf(_swf())])]
    rounds = perf_trend.load_history(hist)
    # journal_fsync p99 blows 10x past history (and > 1 ms absolute)
    bad = _swf(p99={"journal_fsync": 0.040, "data_write": 0.005})
    findings = perf_trend.check(_att_with_swf(bad), rounds)
    hits = [f for f in findings
            if f["check"] == "store-phase-p99-regression"]
    assert len(hits) == 1 and "journal_fsync" in hits[0]["message"]
    # within the 1.5x + 1 ms budget: passes
    ok = _swf(p99={"journal_fsync": 0.0045, "data_write": 0.0055})
    assert not [f for f in
                perf_trend.check(_att_with_swf(ok), rounds)
                if f["check"] == "store-phase-p99-regression"]
    # growth under the absolute 1 ms slack never trips even past 1.5x
    tiny = _swf(p99={"kv_commit": 0.0018})
    assert not [f for f in
                perf_trend.check(_att_with_swf(tiny), rounds)
                if f["check"] == "store-phase-p99-regression"]
    # a fresh run that applied no store transactions self-skips
    idle = _swf(p99={"journal_fsync": 9.0}, txns=0)
    assert not [f for f in
                perf_trend.check(_att_with_swf(idle), rounds)
                if f["check"] == "store-phase-p99-regression"]


def test_store_phase_gate_runs_from_cli(tmp_path, history):
    hist = history + [_hist_round(
        tmp_path, 3, [_att_with_swf(_swf())])]
    fresh = tmp_path / "fresh.json"
    fresh.write_text("\n".join(json.dumps(r) for r in (
        _headline(17.0), _cluster(1.0),
        _att_with_swf(_swf(p99={"journal_fsync": 0.040})))))
    r = _run_cli(fresh, hist)
    assert r.returncode == 1, (r.stdout, r.stderr)
    assert "store-phase-p99-regression" in r.stdout
    assert "journal_fsync" in r.stdout


# ------------------------------------------ ISSUE 15: selftune gate
def _selftune_rec(static=None, tuned=None, trips=0, guards=()):
    return {"metric": "closed-loop selftune attribution (static vs "
                      "self-tuned 1/4/16-client ladder, 3-OSD k=2 "
                      "m=1; value = tuned 16-client MB/s)",
            "value": (tuned or {}).get("16", 0.0), "unit": "MB/s",
            "vs_baseline": 1.0,
            "ladder": {"static": static or
                       {"1": 20.0, "4": 30.0, "16": 25.0},
                       "tuned": tuned or
                       {"1": 21.0, "4": 32.0, "16": 27.0}},
            "tuner": {"counts": {"probe": 6, "kept": 2,
                                 "rolled_back": 1, "neutral": 3,
                                 "guard_trips": trips},
                      "guard_trips": trips,
                      "guards": list(guards),
                      "knobs_kept": ["ec_tpu_inflight_groups"],
                      "knobs_final": {}}}


def test_selftune_gate_passes_when_tuned_holds_every_rung(history):
    rounds = perf_trend.load_history(history)
    assert perf_trend.check(None, rounds,
                            fresh_selftune=_selftune_rec()) == []


def test_selftune_gate_fails_on_lost_rung(history):
    rounds = perf_trend.load_history(history)
    findings = perf_trend.check(
        None, rounds,
        fresh_selftune=_selftune_rec(
            tuned={"1": 21.0, "4": 32.0, "16": 20.0}))
    assert [f["check"] for f in findings] == ["selftune-regression"]
    assert "16-client rung" in findings[0]["message"]
    # equality is NOT a regression: worst case is "changed nothing"
    assert perf_trend.check(
        None, rounds,
        fresh_selftune=_selftune_rec(
            tuned={"1": 20.0, "4": 30.0, "16": 25.0})) == []


def test_selftune_gate_fails_on_guard_trips(history):
    rounds = perf_trend.load_history(history)
    findings = perf_trend.check(
        None, rounds,
        fresh_selftune=_selftune_rec(trips=2,
                                     guards=["slo_burn:client_write",
                                             "overlap_collapse"]))
    assert [f["check"] for f in findings] == ["selftune-guard-trip"]
    assert "slo_burn:client_write" in findings[0]["message"]


def test_selftune_gate_runs_from_cli(tmp_path, history):
    # the record rides a raw bench log next to the k8m4 metrics and
    # run() picks it up by prefix
    bad = tmp_path / "fresh.json"
    bad.write_text("\n".join(json.dumps(r) for r in (
        _headline(17.5), _cluster(1.05),
        _attribution({"queue_wait": 1.1, "encode": 2.1,
                      "commit": 2.9}, 0.97),
        _selftune_rec(tuned={"1": 5.0, "4": 32.0, "16": 27.0}))))
    r = _run_cli(bad, history)
    assert r.returncode == 1
    assert "selftune-regression" in r.stdout
    good = tmp_path / "fresh_ok.json"
    good.write_text("\n".join(json.dumps(r) for r in (
        _headline(17.5), _cluster(1.05),
        _attribution({"queue_wait": 1.1, "encode": 2.1,
                      "commit": 2.9}, 0.97),
        _selftune_rec())))
    r = _run_cli(good, history)
    assert r.returncode == 0, (r.stdout, r.stderr)


# ------------------------- ISSUE 17: bluestore top-hop + ladder gates
def _att_bluestore(top_hop):
    att = _attribution({"queue_wait": 1.0, "encode": 2.0,
                        "commit": 3.0}, 0.95)
    att["osd_objectstore"] = "bluestore"
    att["waterfall"] = {"top_hop": top_hop,
                        "hops": {"store_apply": 0.1}}
    return att


def _store_ladder_rec(blue=None, block=None):
    return {"metric": "store ladder write MB/s (single-OSD "
                      "microbench: memstore vs blockstore vs "
                      "bluestore, qd 1/8/32 x 64 KiB / 1 MiB txns; "
                      "vs_baseline = mean bluestore over mean "
                      "blockstore across rungs)",
            "value": 99.3, "unit": "MB/s", "vs_baseline": 1.56,
            "ladder": {
                "memstore": {"qd1_64k": 670.0, "qd8_64k": 900.0},
                "blockstore": block or {"qd1_64k": 37.8,
                                        "qd8_64k": 35.6,
                                        "qd1_1m": 89.9},
                "bluestore": blue or {"qd1_64k": 50.2,
                                      "qd8_64k": 99.3,
                                      "qd1_1m": 137.1}}}


def test_store_top_hop_gate_fires_on_bluestore(history):
    """With osd_objectstore=bluestore the deferred pipeline must take
    store_apply off the k8m4 top hop — a fresh waterfall still naming
    it means the async rewrite is not deferring (ISSUE 17
    acceptance)."""
    rounds = perf_trend.load_history(history)
    findings = perf_trend.check(_att_bluestore("store_apply"), rounds)
    hits = [f for f in findings if f["check"] == "store-top-hop"]
    assert len(hits) == 1
    assert "store_apply" in hits[0]["message"]
    # any other top hop passes
    assert not [f for f in
                perf_trend.check(_att_bluestore("net_rtt"), rounds)
                if f["check"] == "store-top-hop"]


def test_store_top_hop_gate_skips_on_sync_backends(history):
    """Rounds (and fresh runs) on memstore/blockstore never tagged
    osd_objectstore=bluestore: store_apply on top is the expected
    synchronous shape there, not a finding."""
    att = _attribution({"queue_wait": 1.0, "encode": 2.0,
                        "commit": 3.0}, 0.95)
    att["waterfall"] = {"top_hop": "store_apply"}
    findings = perf_trend.check(att, perf_trend.load_history(history))
    assert not [f for f in findings
                if f["check"] == "store-top-hop"], findings


def test_store_ladder_floor_per_rung(history):
    """bluestore must hold >= STORE_LADDER_FLOOR x blockstore at
    EVERY (queue depth, txn size) rung of the fresh microbench."""
    rounds = perf_trend.load_history(history)
    # healthy ladder (the measured shape) passes
    assert not [f for f in
                perf_trend.check(None, rounds,
                                 fresh_store_ladder=_store_ladder_rec())
                if f["check"] == "store-ladder-regression"]
    # one lost rung fails, and the message names it
    losing = _store_ladder_rec(
        blue={"qd1_64k": 50.2, "qd8_64k": 20.0, "qd1_1m": 137.1})
    findings = perf_trend.check(None, rounds,
                                fresh_store_ladder=losing)
    hits = [f for f in findings
            if f["check"] == "store-ladder-regression"]
    assert len(hits) == 1 and "qd8_64k" in hits[0]["message"]
    # noise slack: a rung within the floor does not trip
    noisy = _store_ladder_rec(
        blue={"qd1_64k": 50.2, "qd8_64k": 35.6 * 0.9,
              "qd1_1m": 137.1})
    assert not perf_trend.check(None, rounds,
                                fresh_store_ladder=noisy)
    # no store_ladder record at all: gate self-skips
    assert not perf_trend.check(None, rounds)


def test_store_gates_run_from_cli(tmp_path, history):
    fresh = tmp_path / "fresh.json"
    fresh.write_text("\n".join(json.dumps(r) for r in (
        _headline(17.5), _cluster(1.05), _att_bluestore("store_apply"),
        _store_ladder_rec(blue={"qd1_64k": 10.0}))))
    r = _run_cli(fresh, history)
    assert r.returncode == 1, (r.stdout, r.stderr)
    assert "store-top-hop" in r.stdout
    assert "store-ladder-regression" in r.stdout
    ok = tmp_path / "fresh_ok.json"
    ok.write_text("\n".join(json.dumps(r) for r in (
        _headline(17.5), _cluster(1.05), _att_bluestore("net_rtt"),
        _store_ladder_rec())))
    r = _run_cli(ok, history)
    assert r.returncode == 0, (r.stdout, r.stderr)


def _rmw_rec(sizes=None, delta=None, full_run=None, vs=2.4):
    return {"metric": "rmw overwrite MB/s (13-OSD k=8 m=4 overwrite "
                      "pool, 64 aio random chunk-aligned sub-stripe "
                      "overwrites per size class; value = delta-path "
                      "4 KiB class, vs_baseline = delta over "
                      "forced-full at 4 KiB)",
            "value": 0.5, "unit": "MB/s", "vs_baseline": vs,
            "sizes": sizes or {
                "4k": {"delta": 0.48, "full": 0.20, "vs_full": 2.4},
                "16k": {"delta": 1.9, "full": 0.8, "vs_full": 2.38},
                "64k": {"delta": 3.1, "full": 3.0, "vs_full": 1.03}},
            "delta": delta or {
                "rmw_ops": 130, "full_ops": 70, "fallbacks": 0,
                "delta_fraction": 0.65,
                "dirty_census": {"1": 64, "4": 66}},
            "full_run": full_run or {"rmw_ops": 0, "full_ops": 200}}


def test_rmw_floor_per_size(history):
    """The delta path must hold >= RMW_FLOOR x the forced full-stripe
    run at EVERY overwrite size of the fresh head-to-head."""
    rounds = perf_trend.load_history(history)
    assert not [f for f in
                perf_trend.check(None, rounds, fresh_rmw=_rmw_rec())
                if f["check"].startswith("rmw-")]
    # a size class losing to the full path fails and is named
    losing = _rmw_rec(sizes={
        "4k": {"delta": 0.1, "full": 0.2, "vs_full": 0.5},
        "16k": {"delta": 1.9, "full": 0.8, "vs_full": 2.38}})
    hits = [f for f in perf_trend.check(None, rounds, fresh_rmw=losing)
            if f["check"] == "rmw-floor"]
    assert len(hits) == 1 and "4k" in hits[0]["message"]
    # exact convergence passes (equality is NOT a regression: the
    # crossover's worst case is "took the full path")...
    even = _rmw_rec(sizes={
        "4k": {"delta": 0.48, "full": 0.20, "vs_full": 2.4},
        "64k": {"delta": 3.0, "full": 3.0, "vs_full": 1.0}})
    assert not [f for f in perf_trend.check(None, rounds,
                                            fresh_rmw=even)
                if f["check"] == "rmw-floor"]
    # ...but ANY size class strictly under 1.0 is one
    under = _rmw_rec(sizes={
        "64k": {"delta": 2.9, "full": 3.0, "vs_full": 0.967}})
    assert [f for f in perf_trend.check(None, rounds,
                                        fresh_rmw=under)
            if f["check"] == "rmw-floor"]
    # no rmw record at all: gate self-skips
    assert not [f for f in perf_trend.check(None, rounds)
                if f["check"].startswith("rmw-")]


def test_rmw_delta_collapse_and_control_leak(history):
    """A delta run where almost nothing took the delta path compared
    full vs full (collapse); delta ops in the forced-off control mean
    the knob leaked — both fail regardless of throughput."""
    rounds = perf_trend.load_history(history)
    collapsed = _rmw_rec(delta={
        "rmw_ops": 3, "full_ops": 197, "fallbacks": 41,
        "delta_fraction": 0.015, "dirty_census": {"1": 3}})
    hits = [f for f in perf_trend.check(None, rounds,
                                        fresh_rmw=collapsed)
            if f["check"] == "rmw-delta-collapse"]
    assert len(hits) == 1 and "41" in hits[0]["message"]
    leaky = _rmw_rec(full_run={"rmw_ops": 55, "full_ops": 145})
    hits = [f for f in perf_trend.check(None, rounds, fresh_rmw=leaky)
            if f["check"] == "rmw-control-leak"]
    assert len(hits) == 1 and "55" in hits[0]["message"]


def test_rmw_history_floor_and_cli(tmp_path, history):
    """vs_baseline is held to ratio_tol x the best rmw-carrying
    history round (older rounds without one silently skip), and the
    whole gate runs end to end from the CLI."""
    with_rmw = history + [_hist_round(tmp_path, 3,
                                      [_cluster(1.0), _rmw_rec(vs=2.5)])]
    rounds = perf_trend.load_history(with_rmw)
    hits = [f for f in
            perf_trend.check(None, rounds, fresh_rmw=_rmw_rec(vs=1.2))
            if f["check"] == "rmw-throughput-regression"]
    assert len(hits) == 1 and "2.500" in hits[0]["message"]
    assert not [f for f in
                perf_trend.check(None, rounds, fresh_rmw=_rmw_rec(vs=2.4))
                if f["check"] == "rmw-throughput-regression"]
    fresh = tmp_path / "fresh_rmw.json"
    fresh.write_text("\n".join(json.dumps(r) for r in (
        _headline(17.5), _cluster(1.05),
        _rmw_rec(sizes={"4k": {"vs_full": 0.4}}))))
    r = _run_cli(fresh, with_rmw)
    assert r.returncode == 1, (r.stdout, r.stderr)
    assert "rmw-floor" in r.stdout
    ok = tmp_path / "fresh_rmw_ok.json"
    ok.write_text("\n".join(json.dumps(r) for r in (
        _headline(17.5), _cluster(1.05), _rmw_rec())))
    r = _run_cli(ok, with_rmw)
    assert r.returncode == 0, (r.stdout, r.stderr)
