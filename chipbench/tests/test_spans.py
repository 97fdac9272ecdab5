"""The division of the chip's idle time among the program's ``serve.*``
spans (``chipbench/spans.py``): exact interval arithmetic on synthetic
spans, a trace without spans, a trace recorded on the chip with them, and
the existing reduction of the committed DIN trace left as it was."""
import gzip
import json
import pathlib

import pytest

from chipbench import spans, trace
from chipbench.tests.test_trace import DIN_SITES, V5E

TESTDATA = pathlib.Path(__file__).resolve().parents[1] / "testdata"
# recorded with chipbench/tools/record_trace.py on one v5e: a 0.05 s
# stretch of paper-ranking.cold-sat with the serve.* spans on
COLD_SAT = TESTDATA / "paper_ranking_cold_sat.xplane.pb.gz"


def _unpack(tmp_path, gz):
    raw = tmp_path / "t.xplane.pb"
    raw.write_bytes(gzip.decompress(gz.read_bytes()))
    return str(raw)


def test_innermost_span_wins_and_a_crossing_span_splits_a_gap():
    worker = [(0, 10, "wait"), (10, 30, "linger"),
              (30, 100, "begin_coalesced"), (40, 70, "pack"),
              (42, 50, "fill"), (50, 60, "h2d"), (70, 90, "dispatch")]
    pieces = spans.innermost(worker)
    assert pieces == [(0, 10, "wait"), (10, 30, "linger"),
                      (30, 40, "begin_coalesced"), (40, 42, "pack"),
                      (42, 50, "fill"), (50, 60, "h2d"), (60, 70, "pack"),
                      (70, 90, "dispatch"), (90, 100, "begin_coalesced")]
    # gaps: across wait into linger, across fill, h2d and their parent,
    # across dispatch's end and begin_coalesced's into no span at all
    gaps = [(5, 15), (45, 65), (85, 120)]
    under, none = spans.divide(gaps, pieces)
    assert dict(under) == {"wait": 5, "linger": 5, "fill": 5, "h2d": 10,
                           "pack": 5, "dispatch": 5, "begin_coalesced": 10}
    assert none == 20
    assert sum(under.values()) + none == sum(e - s for s, e in gaps)


def test_equal_starts_nest_the_shorter_inside():
    pieces = spans.innermost([(0, 50, "stage1"), (0, 20, "stage1_wait"),
                              (5, 5, "empty")])
    assert pieces == [(0, 20, "stage1_wait"), (20, 50, "stage1")]


def test_a_trace_without_spans_divides_nothing(tmp_path):
    red = spans.reduce(_unpack(tmp_path,
                               TESTDATA / "din_zipf_sat.xplane.pb.gz"))
    assert red == {"worker_spans": 0}
    assert spans.shares(red) == {}


def test_the_existing_reduction_of_the_din_trace_is_unchanged(tmp_path):
    """Every key and value ``trace.reduce`` gave the committed DIN trace
    when the spans were added."""
    red = trace.reduce(_unpack(tmp_path,
                               TESTDATA / "din_zipf_sat.xplane.pb.gz"),
                       DIN_SITES, V5E)
    pinned = json.loads((TESTDATA / "din_zipf_sat.reduce.json").read_text())
    assert json.loads(json.dumps(red)) == pinned


def test_a_chip_trace_with_spans_divides_its_idle_time(tmp_path):
    path = _unpack(tmp_path, COLD_SAT)
    red = spans.reduce(path)
    dev = trace.reduce(path, {}, V5E)
    assert red["worker_spans"] > 0
    assert set(red["idle_in_s"]) == set(spans.GROUPS)
    # the gaps are those of the existing reduction's busy union, within
    # the device's extent
    first, last = _device_extent(path)
    assert red["extent_s"] == pytest.approx((last - first) / 1e9, rel=1e-9)
    assert red["idle_s"] + dev["busy_s"] == pytest.approx(red["extent_s"],
                                                          rel=1e-9)
    assert sum(red["idle_s_by_span"].values()) + \
        red["idle_unattributed_s"] == pytest.approx(red["idle_s"], rel=1e-9)
    got = spans.shares(red)
    idle_share = 100.0 * (1.0 - dev["busy_s"] / red["extent_s"])
    assert got["idle"] == pytest.approx(idle_share, rel=1e-9)
    assert set(got) == {f"idle_in_{g}" for g in spans.GROUPS} | {
        "idle", "idle_unattributed", "stage1_device_share"}
    assert all(got[f"idle_in_{g}"] >= 0 for g in spans.GROUPS)
    assert sum(got[f"idle_in_{g}"] for g in spans.GROUPS) + \
        got["idle_unattributed"] <= idle_share + 1e-9
    assert red["span_s"]["pack"] > 0


def _device_extent(path):
    from jax.profiler import ProfileData
    ops = [(e.start_ns, e.end_ns)
           for p in ProfileData.from_file(path).planes
           if p.name.startswith("/device:TPU:")
           for line in p.lines if line.name == "XLA Ops"
           for e in line.events]
    return min(s for s, _ in ops), max(e for _, e in ops)
