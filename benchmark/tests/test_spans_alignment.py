"""The alignment of the host's spans with a device trace, and
``idle_named``, on a recorded window (CPU, no chip time).

``testdata/window_trace.json.gz`` is the profiler's trace of a ``PROFILE
DEVICE`` window on the chip, cut to its device lines and the ``bs/``
annotations; ``testdata/window_spans.json`` is the spans file the worker
wrote beside that directory.  ``testdata/window.expected.json`` says
where both come from and holds numbers worked out apart from the
readers (a 0.1 us raster in NumPy): the bracket's two bounds, the idle
time between programs, and what of it lies under each span.

    python3 -m pytest benchmark/tests/test_spans_alignment.py -q
"""
import gzip
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

from readers import _spans, idle_named            # noqa: E402
from trace_reduce import DeviceTrace              # noqa: E402


def _data(name):
    return os.path.join(BENCH, "testdata", name)


@pytest.fixture(scope="module")
def window():
    with gzip.open(_data("window_trace.json.gz"), "rt") as f:
        raw = json.load(f)
    with open(_data("window_spans.json")) as f:
        doc = json.load(f)
    with open(_data("window.expected.json")) as f:
        want = json.load(f)
    return raw, DeviceTrace.from_chrome(raw), doc, want


def test_chunk_programs_bracket_the_offset(window):
    raw, trace, doc, want = window
    progs = _spans.programs(trace, "jit_run_steps")
    al = _spans.bracket(doc["chunks"], progs)
    # six programs, five chunks: the first five are the window's
    assert len(progs) == 6 and al["pairs"] == len(doc["chunks"]) == 5
    assert al["lo_us"] == pytest.approx(want["bracket_lo_us"], abs=0.2)
    assert al["hi_us"] == pytest.approx(want["bracket_hi_us"], abs=0.2)
    # the mark the worker read back is the host's clock against the
    # profiler's HOST track; here it lies just below the bracket, and
    # the offset used is the bound
    mark = doc["profiler_zero_us"]
    assert al["lo_us"] - mark == pytest.approx(
        want["mark_below_lower_bound_us"], abs=0.5)
    assert _spans.offset(al, mark) == al["lo_us"]
    assert _spans.offset(al, None) == 0.5 * (al["lo_us"] + al["hi_us"])
    assert _spans.offset(al, al["lo_us"] + 7.0) == al["lo_us"] + 7.0
    # a trace that lost its last programs (the converter's million
    # events) pairs the rest and can only widen the bracket
    cut = _spans.bracket(doc["chunks"], progs[:4])
    assert cut["pairs"] == 4
    assert cut["lo_us"] <= al["lo_us"] and cut["hi_us"] >= al["hi_us"]
    assert _spans.bracket(doc["chunks"], []) is None
    # a program longer than the host held its chunk cannot be its own
    long = [(progs[0][0], progs[0][0] + 1e6)]
    assert _spans.bracket(doc["chunks"][:1], long) is None


def test_the_mark_puts_spans_on_their_annotations(window):
    """Each span's own stamp, less the read-back offset, is the start of
    its ``bs/`` twin in the profiler's file to within the annotation's
    own cost."""
    raw, trace, doc, want = window
    zero = doc["profiler_zero_us"]
    for name in ("device_wait", "chunk_edge"):
        twins = sorted(e["ts"] for e in raw["traceEvents"]
                       if e.get("name") == "bs/" + name)
        mine = sorted(s["ts"] for s in doc["spans"] if s["name"] == name)
        mine = mine[len(mine) - len(twins):]    # opened after the mark
        assert twins and len(mine) == len(twins)
        assert max(abs(zero + t - m) for t, m in zip(twins, mine)) \
            < want["annotation_skew_limit_us"]


def test_idle_named_on_the_recorded_window(window, capsys):
    raw, trace, doc, want = window
    al, under, total, ngaps = idle_named.account(doc, trace,
                                                 "jit_run_steps")
    assert al["offset_us"] == al["lo_us"]
    assert ngaps == want["gaps_between_programs"]
    assert total == pytest.approx(want["idle_between_programs_us"],
                                  abs=0.01)
    for name, us in want["idle_under_us"].items():
        assert under.get(name, 0.0) == pytest.approx(us, abs=1.0), name
    assert total - sum(under.values()) == pytest.approx(
        want["idle_under_no_span_us"], abs=1.0)
    ctx = {"trace": trace, "tracedir": _data("window")}
    got = idle_named.read(ctx, {"program": "jit_run_steps"})
    assert got == pytest.approx(want["idle_named_pct"], abs=0.02)
    err = capsys.readouterr().err
    assert "bs/clock mark" in err and "moved to the bound" in err \
        and "us wide" in err


def test_no_spans_file_reads_as_nothing(window, tmp_path):
    """A program that writes no spans file (the parent of the PR that
    added the reader): None, and no raise."""
    raw, trace, doc, want = window
    ctx = {"trace": trace, "tracedir": str(tmp_path / "devprof")}
    assert idle_named.read(ctx, {"program": "jit_run_steps"}) is None
    assert idle_named.read({"trace": None, "tracedir": _data("window")},
                           {"program": "jit_run_steps"}) is None
