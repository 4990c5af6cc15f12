"""What window kind "stream" adds to "advance", on a recorded session:
the gaps between ACDATA arrivals inside the window, and the chunks of a
simulated second from the worker's count between the two METRICS DUMP
echoes and the simulated time SIMINFO carried past the client."""
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from windows import advance, stream             # noqa: E402


def test_simulated_time_at_a_stamp_is_carried_on_from_the_frames_before():
    info = [(10.0, 100.0), (11.0, 122.0), (12.0, 144.0), (13.0, 150.0)]
    assert stream._simt_at(info, 11.5) == pytest.approx(133.0)
    # the frame after the stamp (a HOLD came in between) is not used
    assert stream._simt_at(info, 12.5) == pytest.approx(155.0)
    assert stream._simt_at(info, 10.5) is None


def test_stream_is_advance_plus_frame_gaps_and_counted_chunks(monkeypatch):
    m0 = "sim registry:\nsim_chunk_latency_ms: n=100 mean=45"
    m1 = "sim registry:\nsim_chunk_latency_ms: n=1222 mean=45"
    # frames every 0.2 s, one in ten 0.1 s late; the window is [20, 71]
    arrivals = [0.2 * k + (0.1 if k % 10 == 0 else 0.0)
                for k in range(50, 400)]
    s = types.SimpleNamespace(
        acdata_t=arrivals,
        echo=[(19.5, m0, b"w"), (71.5, m1, b"w")],
        siminfo=[(float(t), 22.0 * t) for t in range(10, 80)])
    sv = types.SimpleNamespace(s=s)
    monkeypatch.setattr(advance, "run", lambda *a: dict(
        q={"setup_s": 20.0, "advance_rate": 22.0},
        ctx=dict(t_open=20.0, t_close=71.0, m0={b"w": m0}, m1={b"w": m1},
                 chunks_per_unit=1.0 / 22.0), note="51 advances"))
    out = stream.run(sv, {}, {"chunk_counter": "sim_chunk_latency_ms"},
                     {}, None, "")
    assert out["q"]["advance_rate"] == 22.0
    assert 290.0 <= out["q"]["frame_gap_p95_ms"] <= 310.0
    # 1122 chunks over 52 s x 22 sim-s/s
    assert out["ctx"]["chunks_per_unit"] == pytest.approx(1122 / 1144.0)
