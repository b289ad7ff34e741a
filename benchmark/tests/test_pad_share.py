"""dispatch.pad_share's reader on a span list written out by hand,
runnable on a CPU:

    python3 -m pytest benchmark/tests/test_pad_share.py -q
"""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from harness import spec, trace  # noqa: E402

MIB = 1 << 20


def spans_of(h2d: list) -> dict:
    """One thread, a window of [0, 1000) ns, the given dispatch.h2d
    keyword sets inside it and one more outside it."""
    line = [(trace.WINDOW_SPAN, 0.0, 1000.0, {})]
    line += [("dispatch.h2d", 100.0 * i + 10, 20.0, meta)
             for i, meta in enumerate(h2d)]
    line.append(("dispatch.h2d", 5000.0, 20.0,
                 {"bytes": 64 * MIB, "live_bytes": 0, "batch": 128}))
    return {"lines": [line], "device_ops": []}


def read(h2d: list):
    return spec.metric_reader("dispatch.pad_share").read(
        {"spans": spans_of(h2d)})


def test_a_4m_object_on_640k_stripes_stages_an_eighth_of_padding():
    # 7 stripes of [10, 65536] in a bucket of 8 (the 7th stripe's own
    # zero tail is the PG's, copied like payload)
    assert read([{"bytes": 5 * MIB, "live_bytes": 7 * 655360,
                  "batch": 8}]) == pytest.approx(12.5)
    # and with a CRC dispatch of 4 KiB extents, 100 of a bucket of 128
    assert read([{"bytes": 5 * MIB, "live_bytes": 7 * 655360, "batch": 8},
                 {"bytes": 128 * 4096, "live_bytes": 100 * 4096,
                  "batch": 128}]) == pytest.approx(
        100 * (1 - (7 * 655360 + 100 * 4096) / (5 * MIB + 128 * 4096)))


def test_exact_buckets_read_zero():
    assert read([{"bytes": 4 * MIB, "live_bytes": 4 * MIB,
                  "batch": 128}]) == 0.0


@pytest.mark.parametrize("h2d", [
    [],                                         # no dispatch in the window
    [{"bytes": 5 * MIB, "batch": 8}],           # a program without the keyword
])
def test_nothing_to_read_is_none(h2d):
    assert read(h2d) is None


def test_the_metric_is_declared_for_the_cell_that_pads():
    rows = [m for m in spec.benchmark()["per_layer"]
            if m["name"] == "dispatch.pad_share"]
    assert rows == [{"name": "dispatch.pad_share", "unit": "%",
                     "better": "lower", "source": "program_span",
                     "layer": "dispatch", "moves": "throughput",
                     "workloads": ["cauchy_k10m4.write_4m"]}]
    reader = spec.metric_reader("dispatch.pad_share")
    assert (reader.SOURCE, reader.LAYER, reader.MOVES) == (
        "program_span", "dispatch", "throughput")
