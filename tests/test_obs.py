"""Observability subsystem (ISSUE-11): metrics registry, flight
recorder, sim instrumentation, fleet aggregation.

Contracts pinned here:

* Registry units — histogram bucket placement + percentile estimates,
  delta shipping (increments exactly once), merge commutativity (the
  fleet aggregate equals the per-worker sums regardless of heartbeat
  interleaving), Prometheus exposition format.
* Recorder — ring stays bounded, disabled path is a shared no-op (no
  events, no allocation), dumps are valid Chrome/Perfetto trace-event
  JSON.
* Off-path parity — a run with the recorder ENABLED is bit-identical
  to one with it disabled: the instrumentation is host-side only.
* Incident auto-dump — a FAULT NAN guard trip leaves a trace dump on
  disk with the guard_trip instant in it.
* Fleet aggregation e2e — one real worker's heartbeat obs deltas land
  in the server's fleet registry; METRICS round-trips to a client.
* The multi-reason sync accounting fix — a chunk held back by two
  co-occurring reasons counts BOTH (the old code recorded reasons[0]
  only).
* One tree per piece and per chunk (ISSUE-25) — a two-piece BATCH
  through a detached node yields piece > piece_reset / stack_run /
  chunk_dispatch / chunk_edge > device_wait with shared ``piece`` and
  ``seq`` tags; the always-on series split an edge retirement into the
  wait and the work; the lowered chunk program does not depend on the
  recorder.
"""
import hashlib
import json
import threading
import time

import numpy as np
import pytest

from bluesky_tpu import settings
from bluesky_tpu.obs.metrics import (DEFAULT_S_BUCKETS, Counter, Gauge,
                                     Histogram, Registry)
from bluesky_tpu.obs.trace import _NULL_SPAN, Recorder, get_recorder
from bluesky_tpu.simulation.sim import Simulation


@pytest.fixture()
def sim():
    return Simulation(nmax=16)


@pytest.fixture(autouse=True)
def _recorder_reset():
    """The recorder is a process singleton: leave it disabled+empty."""
    rec = get_recorder()
    yield
    rec.disable()
    rec.clear()


def do(sim, *lines):
    for line in lines:
        sim.stack.stack(line)
    sim.stack.process()
    out = "\n".join(sim.scr.echobuf)
    sim.scr.echobuf.clear()
    return out


def _fleet(sim, n=3):
    for i in range(n):
        do(sim, f"CRE KL{i} B744 {52 + i} {4 + i} 90 FL{200 + 10 * i} 250")
    sim.op()
    sim.run(until_simt=2.0)


def state_hash(sim):
    import jax
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(jax.tree.map(np.asarray, sim.traf.state)):
        h.update(np.ascontiguousarray(leaf).tobytes())
    return h.hexdigest()


# ----------------------------------------------------------- registry units
class TestRegistry:
    def test_counter_and_gauge(self):
        reg = Registry()
        c = reg.counter("reqs", help="requests")
        c.inc()
        c.inc(2.5)
        assert c.value == 3.5
        g = reg.gauge("depth")
        g.set(7)
        g.inc(-2)
        assert g.value == 5.0
        # get-or-create returns the same instance
        assert reg.counter("reqs") is c
        assert reg.get("depth") is g

    def test_kind_mismatch_raises(self):
        reg = Registry()
        reg.counter("x")
        with pytest.raises(TypeError):
            reg.gauge("x")

    def test_histogram_buckets_and_percentiles(self):
        h = Histogram("lat", buckets=(1.0, 10.0, 100.0))
        for v in (0.5, 0.7, 5.0, 50.0, 500.0):
            h.observe(v)
        # bucket ownership: [<=1, <=10, <=100, overflow]
        assert h.counts == [2, 1, 1, 1]
        assert h.count == 5 and h.sum == pytest.approx(556.2)
        assert h.mean == pytest.approx(556.2 / 5)
        # p50 falls in the (1, 10] bucket; overflow pins to last bound
        assert 1.0 <= h.percentile(0.5) <= 10.0
        assert h.percentile(1.0) == 100.0
        assert Histogram("e").percentile(0.5) == 0.0

    def test_unsorted_buckets_rejected(self):
        with pytest.raises(ValueError):
            Histogram("bad", buckets=(10.0, 1.0))

    def test_delta_ships_increments_exactly_once(self):
        reg = Registry()
        reg.counter("c").inc(3)
        reg.histogram("h", buckets=(1.0, 10.0)).observe(5.0)
        reg.gauge("g").set(4)
        d1 = reg.delta()
        assert d1["c"]["value"] == 3
        assert d1["h"]["count"] == 1 and d1["h"]["counts"] == [0, 1, 0]
        assert d1["g"]["value"] == 4
        # no change -> counters/histograms omitted, gauges still ship
        d2 = reg.delta()
        assert "c" not in d2 and "h" not in d2 and d2["g"]["value"] == 4
        # only the increment since the last call ships
        reg.counter("c").inc(2)
        assert reg.delta()["c"]["value"] == 2

    def test_merge_is_order_independent(self):
        """Two workers' interleaved deltas aggregate exactly."""
        w1, w2 = Registry(), Registry()
        fleet_a, fleet_b = Registry(), Registry()
        for i in range(5):
            w1.counter("chunks").inc()
            w1.histogram("lat").observe(1.0 + i)
            w2.counter("chunks").inc(2)
            w2.histogram("lat").observe(10.0 * (i + 1))
            d1, d2 = w1.delta(), w2.delta()
            fleet_a.merge(d1)
            fleet_a.merge(d2)
            fleet_b.merge(d2)          # reversed arrival order
            fleet_b.merge(d1)
        for fleet in (fleet_a, fleet_b):
            assert fleet.counter("chunks").value == 15
            h = fleet.get("lat")
            assert h.count == 10
            assert h.sum == pytest.approx(sum(1.0 + i for i in range(5))
                                          + sum(10.0 * (i + 1)
                                                for i in range(5)))

    def test_prometheus_text_cumulative_buckets(self):
        reg = Registry()
        h = reg.histogram("lat_ms", buckets=(1.0, 10.0))
        h.observe(0.5)
        h.observe(5.0)
        h.observe(50.0)
        txt = reg.prometheus_text()
        assert "# TYPE lat_ms histogram" in txt
        assert 'lat_ms_bucket{le="1"} 1' in txt
        assert 'lat_ms_bucket{le="10"} 2' in txt       # cumulative
        assert 'lat_ms_bucket{le="+Inf"} 3' in txt
        assert "lat_ms_count 3" in txt

    def test_prometheus_text_order_is_registration_independent(self):
        """Exported files must diff cleanly between scrapes: series are
        emitted in sorted-name order regardless of which code path
        registered them first.  Lazily-registered series (the scanstats
        drain registers on the first drained chunk) would otherwise
        reshuffle the whole file mid-run."""
        def fill(reg, names):
            for n in names:
                if n.startswith("h_"):
                    reg.histogram(n, buckets=(1.0, 10.0)).observe(5.0)
                elif n.startswith("g_"):
                    reg.gauge(n).set(2)
                else:
                    reg.counter(n).inc(3)
        names = ["c_steps", "h_lat", "g_depth", "c_chunks", "h_conf"]
        a, b = Registry(), Registry()
        fill(a, names)
        fill(b, names[::-1])         # reversed registration order
        assert a.prometheus_text() == b.prometheus_text()
        emitted = [ln.split()[2] for ln in
                   a.prometheus_text().splitlines()
                   if ln.startswith("# TYPE")]
        assert emitted == sorted(emitted)

    def test_histogram_add_counts_merges_exactly(self):
        """``add_counts`` (the scanstats drain path) must be count-
        equivalent to observing the same values: bucket counts, total
        count and sum all merge exactly — and a mis-sized vector is
        refused, never silently misaligned."""
        obs = Histogram("x", buckets=(1.0, 10.0))
        for v in (0.5, 5.0, 5.0, 50.0):
            obs.observe(v)
        dev = Histogram("x", buckets=(1.0, 10.0))
        dev.add_counts([1, 2, 1], sum=60.5)
        assert dev.counts == obs.counts
        assert dev.count == obs.count
        assert dev.sum == pytest.approx(obs.sum)
        with pytest.raises(ValueError):
            dev.add_counts([1, 2])

    def test_export_atomic(self, tmp_path):
        reg = Registry()
        reg.counter("c").inc()
        p = tmp_path / "metrics" / "prom.txt"
        assert reg.export(str(p)) == str(p)
        assert "# TYPE c counter" in p.read_text()
        # rate limit: second maybe_export inside the interval is a no-op
        assert reg.maybe_export(str(p), interval=100.0) == str(p)
        reg.counter("c").inc()
        assert reg.maybe_export(str(p), interval=100.0) is None

    def test_text_empty_and_snapshot(self):
        reg = Registry()
        assert reg.text() == "(no metrics registered)"
        reg.histogram("h").observe(2.0)
        snap = reg.snapshot()
        assert snap["h"]["type"] == "histogram"
        assert snap["h"]["count"] == 1


# --------------------------------------------------------- flight recorder
class TestRecorder:
    def test_ring_is_bounded(self):
        rec = Recorder(maxlen=16)
        rec.enable()
        for i in range(50):
            rec.instant("tick", i=i)
        assert len(rec) == 16 == rec.maxlen
        # oldest events were evicted, newest kept
        assert rec._ring[-1]["args"]["i"] == 49

    def test_disabled_is_a_shared_noop(self):
        rec = Recorder(maxlen=16)
        assert rec.span("x") is _NULL_SPAN
        with rec.span("x", seq=1):
            pass
        rec.instant("y")
        rec.complete("z", 0.0, 1.0)
        assert len(rec) == 0
        assert rec.dump() is None          # empty ring -> no file

    def test_events_carry_perfetto_keys(self):
        rec = Recorder(maxlen=64)
        rec.enable()
        with rec.span("chunk_dispatch", seq=3, chunk=20):
            time.sleep(0.001)
        rec.instant("guard_trip", cat="sim", action="quarantine")
        rec.complete("chunk_edge", rec.wall_us(), 123.0, seq=3)
        evs = list(rec._ring)
        assert [e["ph"] for e in evs] == ["X", "i", "X"]
        for e in evs:
            for key in ("name", "cat", "ph", "ts", "pid", "tid", "args"):
                assert key in e
        assert evs[0]["dur"] > 0
        assert evs[0]["args"]["seq"] == 3

    def test_dump_is_valid_trace_event_json(self, tmp_path):
        rec = Recorder(maxlen=64)
        rec.enable()
        with rec.span("sort_refresh", backend="tiled"):
            pass
        rec.instant("hedge", cat="server", piece="CASE_A")
        p = tmp_path / "t.json"
        assert rec.dump(str(p)) == str(p)
        doc = json.loads(p.read_text())
        assert isinstance(doc["traceEvents"], list)
        assert len(doc["traceEvents"]) == 2
        for ev in doc["traceEvents"]:
            assert ev["ph"] in ("X", "i")
            assert isinstance(ev["ts"], float)
            assert isinstance(ev["pid"], int)
        # the ring is not cleared by a dump
        assert len(rec) == 2

    def test_trace_report_merges_and_tables(self, tmp_path):
        rec = Recorder(maxlen=64)
        rec.enable()
        with rec.span("chunk_dispatch", seq=1, chunk=20, world=0):
            pass
        rec.complete("chunk_edge", rec.wall_us(), 50.0, seq=1,
                     latency_ms=0.5)
        rec.instant("chunk_voided", seq=1, epoch=0)
        p = tmp_path / "a.json"
        rec.dump(str(p))
        import sys
        sys.path.insert(0, "scripts")
        import trace_report
        events = trace_report.load([str(p)])
        assert len(events) == 3
        rows, loose = trace_report.chunk_table(events)
        assert len(rows) == 1 and not loose
        row = next(iter(rows.values()))
        assert row["chunk"] == 20
        assert "chunk_dispatch" in row and "chunk_edge" in row
        assert row["events"] == ["chunk_voided"]

    def test_overlapping_dumps_dedupe_on_merge(self, tmp_path):
        """Two dumps of one ring overlap (dumps never clear the ring):
        the throttled guard-trip auto-dump and a later manual TRACE
        DUMP both carry the incident events.  trace_report.load must
        fold the shared prefix to ONE copy of each event, so a chunk
        never shows up twice in the merged table."""
        rec = Recorder(maxlen=64)
        rec.enable()
        with rec.span("chunk_dispatch", seq=1, chunk=20):
            pass
        rec.instant("guard_trip", cat="sim", action="halt", seq=1)
        p1 = tmp_path / "auto.json"
        rec.dump(str(p1), reason="guard_trip")     # auto-dump snapshot
        # the run continues; the later manual dump repeats both events
        rec.complete("chunk_edge", rec.wall_us(), 40.0, seq=1,
                     latency_ms=0.4)
        with rec.span("chunk_dispatch", seq=2, chunk=20):
            pass
        p2 = tmp_path / "manual.json"
        rec.dump(str(p2), reason="manual")
        assert len(json.loads(p2.read_text())["traceEvents"]) == 4
        import sys
        sys.path.insert(0, "scripts")
        import trace_report
        events = trace_report.load([str(p1), str(p2)])
        assert len(events) == 4            # 2 shared events folded
        rows, loose = trace_report.chunk_table(events)
        assert set(k[1] for k in rows) == {1, 2} and not loose
        row1 = rows[next(k for k in rows if k[1] == 1)]
        assert row1["events"] == ["guard_trip"]   # once, not twice


# ------------------------------------------------------- sim instrumentation
class TestSimInstrumentation:
    def test_chunk_metrics_populate(self, sim):
        _fleet(sim)
        lat = sim.obs.get("sim_chunk_latency_ms")
        assert lat.count > 0
        assert sim.pipe_stats["pipelined_chunks"] \
            + sim.pipe_stats["sync_chunks"] == lat.count
        # deleted with its site in PR 35: no metric read it since PR 31
        assert sim.obs.get("sim_dispatch_gap_ms") is None
        # registries are per-sim: a second sim starts clean
        assert Simulation(nmax=16).obs.get(
            "sim_chunk_latency_ms").count == 0

    def test_recorder_on_is_bit_identical(self, sim):
        rec = get_recorder()
        rec.disable()
        _fleet(sim)
        h_off = state_hash(sim)
        sim2 = Simulation(nmax=16)
        rec.enable()
        _fleet(sim2)
        h_on = state_hash(sim2)
        assert h_off == h_on
        assert len(rec) > 0        # the enabled run did record spans

    def test_recorder_on_emits_chunk_spans(self, sim):
        rec = get_recorder()
        rec.clear()
        rec.enable()
        _fleet(sim)
        names = {e["name"] for e in rec._ring}
        assert "chunk_dispatch" in names and "chunk_edge" in names
        # correlation: every dispatch span carries a seq tag
        seqs = [e["args"]["seq"] for e in rec._ring
                if e["name"] == "chunk_dispatch"]
        assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)

    def test_guard_trip_autodumps(self, sim, tmp_path, monkeypatch):
        monkeypatch.setattr(settings, "trace_dir", str(tmp_path))
        rec = get_recorder()
        rec.clear()
        rec.enable()
        sim.pipeline_enabled = False
        _fleet(sim)
        do(sim, "FAULT NAN KL1")
        sim.op()
        sim.run(until_simt=sim.simt + 1.5)
        assert len(sim.guard.trips) == 1
        assert sim.obs.counter("sim_guard_trips").value == 1
        dumps = list(tmp_path.glob("trace-sim-*-guard_trip.json"))
        assert len(dumps) == 1
        doc = json.loads(dumps[0].read_text())
        trips = [e for e in doc["traceEvents"]
                 if e["name"] == "guard_trip"]
        assert trips and trips[0]["args"]["action"]

    def test_autodump_respects_the_knob(self, sim, tmp_path, monkeypatch):
        monkeypatch.setattr(settings, "trace_dir", str(tmp_path))
        monkeypatch.setattr(settings, "trace_autodump", False)
        rec = get_recorder()
        rec.enable()
        sim.pipeline_enabled = False
        _fleet(sim)
        do(sim, "FAULT NAN KL1")
        sim.op()
        sim.run(until_simt=sim.simt + 1.5)
        assert sim.obs.counter("sim_guard_trips").value == 1
        assert not list(tmp_path.glob("trace-*.json"))

    def test_mesh_kill_voids_the_inflight_chunk(self, sim, tmp_path,
                                                monkeypatch):
        """A device-group loss while a pipelined chunk is in flight
        leaves the full incident story on the timeline: chunk_voided
        (the edge that rode the dead mesh) then the mesh_lost ->
        resharded pair, plus a throttled auto-dump on disk."""
        monkeypatch.setattr(settings, "trace_dir", str(tmp_path))
        rec = get_recorder()
        rec.clear()
        rec.enable()
        _fleet(sim)
        do(sim, "SHARD REPLICATE 8")
        sim.op()
        sim.fastforward()
        for _ in range(3):
            sim.step()
        assert sim._inflight
        voided_seq = sim._inflight[-1].seq
        sim.mesh_guard.kill_group(1)       # mid-flight, not at an edge
        for _ in range(3):
            sim.step()
        sim.drain_pipeline()
        names = [e["name"] for e in rec._ring]
        i_void = names.index("chunk_voided")
        i_lost = names.index("mesh_lost")
        i_resh = names.index("resharded")
        assert i_void < i_lost < i_resh
        assert sim.obs.counter("sim_mesh_trips").value == 2
        void = list(rec._ring)[i_void]
        assert void["args"]["seq"] == voided_seq
        assert void["args"]["epoch"] == 0
        assert list(tmp_path.glob("trace-sim-*-mesh_trip.json"))

    def test_multi_reason_sync_counts_every_reason(self, sim):
        """A chunk held back by two co-occurring reasons is one sync
        chunk but TWO reasons (the old code recorded reasons[0] only)."""
        sim.pipeline_enabled = False          # reason "off"
        sim.guard.set_policy("halt")          # reason "guard-halt"
        _fleet(sim)
        reasons = dict(sim.pipe_stats["sync_reasons"].items())
        assert reasons["off"] >= 1
        assert reasons["guard-halt"] == reasons["off"]

    def test_metrics_dump_detached(self, sim):
        _fleet(sim)
        out = do(sim, "METRICS DUMP")
        assert "sim registry:" in out
        assert "sim_chunk_latency_ms" in out
        # the bare sector-metrics readback is untouched
        assert "OFF" in do(sim, "METRICS")

    def test_trace_command_cycle(self, sim, tmp_path, monkeypatch):
        monkeypatch.setattr(settings, "trace_dir", str(tmp_path))
        assert "TRACE OFF" in do(sim, "TRACE")
        do(sim, "TRACE ON")
        assert get_recorder().enabled
        _fleet(sim)
        out = do(sim, "TRACE DUMP")
        assert "Trace written to" in out
        assert list(tmp_path.glob("trace-sim-*-manual.json"))
        do(sim, "TRACE OFF")
        assert not get_recorder().enabled
        assert "TRACE OFF" in do(sim, "TRACE")


# ------------------------------------------------------- piece/chunk trees
def _piece(name, hold_at=3.0):
    return {"scentime": [0.0, 0.0, 0.0, 0.0, hold_at],
            "scencmd": [f"SCEN {name}",
                        "CRE KL1 B744 52 4 90 FL200 250",
                        "CRE KL2 B744 52.5 4 270 FL200 250", "FF",
                        "HOLD"]}


def _run_pieces(node, names, hold_at=3.0):
    from bluesky_tpu.simulation.sim import OP
    for name in names:
        node.event(b"BATCH", _piece(name, hold_at), [])
        for _ in range(200):
            node.step()
            if node.sim.state_flag != OP and node._piece_span is None:
                break
        assert node._piece_span is None, f"piece {name} never ended"
        node.step()                 # one idle turn of the loop


class TestPieceTree:
    @pytest.fixture()
    def traced(self):
        """A detached node's ring after a two-piece BATCH."""
        from bluesky_tpu.simulation.simnode import DetachedSimNode
        rec = get_recorder()
        rec.clear()
        rec.enable()
        node = DetachedSimNode(nmax=16)
        _run_pieces(node, ("CASE_A", "CASE_B"))
        spans = [e for e in rec._ring if e["ph"] == "X"]
        return node, spans, {e["id"]: e for e in spans}

    def test_two_pieces_yield_the_span_tree(self, traced):
        node, spans, by_id = traced

        def parent(e):
            par = by_id.get(e["parent"])
            return par["name"] if par else None

        want = {"piece": {None}, "piece_reset": {"piece"},
                # these pieces load no SYN scenario: RESET's alone
                # (tests/test_make_state.py has the one under stack_run)
                "make_state": {"piece_reset"},
                "stack_run": {"piece"}, "chunk_dispatch": {"piece"},
                "chunk_edge": {"piece"}, "device_wait": {"chunk_edge"},
                # the loop idles between pieces and never inside one:
                # the broker hears of the HOLD in the turn that took it
                "node_idle": {None}}
        got = {}
        for e in spans:
            got.setdefault(e["name"], set()).add(parent(e))
        # the 5 Hz stream frame is built whoever listens, wherever the
        # wall clock puts it
        assert got.pop("acdata_frame", set()) <= {None, "piece"}
        # the write program runs under whoever reads the state first: a
        # command of the pass, or the flush ahead of the dispatch
        assert got.pop("state_write") <= {"stack_run", "piece"}
        assert got == want
        pieces = [e for e in spans if e["name"] == "piece"]
        assert [e["args"]["piece"] for e in pieces] == ["CASE_A",
                                                         "CASE_B"]
        for e in spans:
            if e["parent"] is None:
                continue
            # every span below a piece carries that piece's name, and
            # lies inside its parent
            top = e
            while top["parent"] is not None:
                par = by_id[top["parent"]]
                assert par["ts"] <= top["ts"] and top["ts"] + top["dur"] \
                    <= par["ts"] + par["dur"] + 1.0
                top = par
            assert e["args"]["piece"] == top["args"]["piece"]
        # a chunk's spans share its seq: every dispatch has one edge,
        # and the edge's wait inherits the tag
        seqs = [e["args"]["seq"] for e in spans
                if e["name"] == "chunk_dispatch"]
        assert len(seqs) >= 4 and sorted(
            e["args"]["seq"] for e in spans
            if e["name"] == "chunk_edge") == seqs
        for e in spans:
            if e["name"] == "device_wait":
                assert e["args"]["seq"] == by_id[e["parent"]]["args"]["seq"]
        runs = [e["args"] for e in spans if e["name"] == "stack_run"]
        assert [r["first"] for r in runs] == ["SCEN", "HOLD"] * 2
        assert runs[0]["n"] == 4 and runs[1]["n"] == 1

    def test_edge_is_wait_plus_work_and_pieces_are_booked(self, traced):
        """chunk_edge self time plus device_wait is its duration: the
        always-on series (program clock) against the spans (recorder
        stamps), two measurements of the same retirements."""
        node, spans, by_id = traced
        obs = node.sim.obs
        edges = [e for e in spans if e["name"] == "chunk_edge"]
        waits = [e for e in spans if e["name"] == "device_wait"]
        assert len(waits) == len(edges) \
            == obs.get("sim_edge_work_ms").count \
            == obs.get("sim_device_wait_ms").count \
            == obs.get("sim_chunk_latency_ms").count
        span_ms = sum(e["dur"] for e in edges) * 1e-3
        wait_ms = sum(e["dur"] for e in waits) * 1e-3
        assert obs.get("sim_device_wait_ms").sum == pytest.approx(
            wait_ms, abs=0.05 * len(edges))
        assert obs.get("sim_edge_work_ms").sum \
            + obs.get("sim_device_wait_ms").sum == pytest.approx(
                span_ms, abs=0.05 * len(edges))
        assert obs.get("sim_piece_reset_ms").count == 2
        assert obs.get("sim_stack_ms").count == 4
        # STATECHANGE sent -> next BATCH handled: one turn between two
        assert obs.get("sim_piece_turnaround_ms").count == 1
        # a step driven by hand does not sleep: the idle loop's pace
        # is its caller's wait (Node.run), not the step's
        assert obs.get("sim_piece_turnaround_ms").sum < 15.0

    def test_trace_report_prints_self_times(self, traced, tmp_path):
        node, spans, by_id = traced
        import sys
        sys.path.insert(0, "scripts")
        import trace_report
        p = tmp_path / "ring.json"
        get_recorder().dump(str(p))
        table = trace_report.self_times(trace_report.load([str(p)]))
        n, total, own = table["chunk_edge"]
        waits = sum(e["dur"] for e in spans
                    if e["name"] == "device_wait") * 1e-3
        assert n == table["device_wait"][0]
        assert own == pytest.approx(total - waits, abs=1e-6)
        assert table["piece"][2] < table["piece"][1]


# ------------------------------------------- a piece's account (ISSUE-40)
# scope directly beneath ``piece`` -> the series observed at its site
PART_SERIES = {"piece_reset": ("sim_piece_reset_ms",),
               "stack_run": ("sim_stack_ms",),
               "chunk_dispatch": ("sim_dispatch_ms",),
               "chunk_edge": ("sim_edge_work_ms", "sim_device_wait_ms"),
               "acdata_frame": ("sim_frame_ms",),
               "node_poll": ("sim_node_poll_ms",)}


class _NoBroker:
    """A worker's event socket with nobody behind it: a poll waits its
    timeout out and finds nothing, what is sent is dropped."""

    def poll(self, timeout_ms):
        time.sleep(timeout_ms * 1e-3)
        return 0

    def send_multipart(self, frames):
        pass

    def close(self):
        pass


class TestTimedScope:
    def test_recorder_off_observes_and_records_nothing(self):
        from bluesky_tpu.obs.trace import Timed
        rec = Recorder(maxlen=16)
        reg = Registry()
        timed = Timed(reg, recorder=rec)
        with timed("outer", "outer_ms", seq=1) as outer:
            with timed("inner", "inner_ms") as inner:
                time.sleep(0.002)
                inner.tag(n=3)               # a no-op, not an error
            with timed("inner"):             # no series: booked all the same
                pass
            with timed(None, "bare_ms"):     # a series and no span
                pass
        assert outer.span is None and inner.span is None
        assert len(rec) == 0 and not rec.scopes()
        assert reg.get("outer_ms").count == reg.get("inner_ms").count == 1
        assert reg.get("inner_ms").sum == inner.ms >= 2.0
        assert set(outer.parts) == {"inner", "bare_ms"}
        assert outer.parts["inner"] > inner.ms
        assert outer.own_ms == pytest.approx(
            outer.ms - sum(outer.parts.values()), abs=1e-9)
        assert 0.0 <= outer.own_ms < outer.ms

    def test_recorder_on_adds_the_span(self):
        from bluesky_tpu.obs.trace import Timed
        rec = Recorder(maxlen=16)
        rec.enable()
        timed = Timed(Registry(), recorder=rec)
        piece = timed.begin("piece", cat="node", piece="CASE_A")
        with timed("stack_run", "sim_stack_ms", n=1) as sc:
            sc.tag(n=2)
            with timed(None, "bare_ms") as bare:
                pass
        timed.end(piece, done=True)
        assert bare.span is None             # no name, no span
        evs = {e["name"]: e for e in rec._ring}
        assert set(evs) == {"piece", "stack_run"}
        assert evs["stack_run"]["parent"] == evs["piece"]["id"]
        assert evs["stack_run"]["args"] == {"n": 2, "piece": "CASE_A"}
        assert evs["piece"]["args"]["done"] is True
        assert evs["piece"]["cat"] == "node"
        # the two clocks bracket each other: span inside the scope
        assert evs["stack_run"]["dur"] * 1e-3 <= sc.ms
        assert piece.parts == {"stack_run": sc.ms}


class TestPieceAccount:
    @pytest.fixture()
    def worker(self):
        """A networked worker with no broker behind its socket, its
        loop turned by hand: poll, then step."""
        pytest.importorskip("zmq")
        from bluesky_tpu.simulation.simnode import SimNode
        from tests.test_network import free_ports
        wev, wst = free_ports(2)
        node = SimNode(event_port=wev, stream_port=wst, nmax=16)
        node.event_io.close()
        node.event_io = _NoBroker()
        yield node
        node.close()

    @staticmethod
    def _sums(obs):
        return {h: obs.get(h).sum
                for hs in PART_SERIES.values() for h in hs}

    def test_two_pieces_are_accounted_for_to_the_microsecond(
            self, worker):
        from bluesky_tpu.simulation.sim import OP
        rec = get_recorder()
        rec.clear()
        rec.enable()
        node, obs = worker, worker.sim.obs
        node.sim.scr._next_acdata = 0.0      # a frame in the first turn
        deltas = []
        for name in ("CASE_A", "CASE_B"):
            node._end_idle()         # the stretch between two pieces
            before = self._sums(obs)
            node.event(b"BATCH", _piece(name), [])
            for _ in range(400):
                node.process_events(timeout_ms=1)
                node.step()
                if node.sim.state_flag != OP and node._piece_span is None:
                    break
            assert node._piece_span is None, f"piece {name} never ended"
            after = self._sums(obs)
            deltas.append({h: after[h] - before[h] for h in after})
            node.step()              # one idle turn of the loop: the
            node.process_events(timeout_ms=1)      # step, then its wait
        node._end_idle()
        pieces = [e for e in rec._ring if e["name"] == "piece"]
        assert [e["args"]["piece"] for e in pieces] == ["CASE_A",
                                                         "CASE_B"]
        assert obs.get("sim_piece_ms").count == 2 \
            == obs.get("sim_piece_own_ms").count
        seen = set()
        for e, delta in zip(pieces, deltas):
            parts, own = e["args"]["parts"], e["args"]["own_ms"]
            seen |= set(parts)
            assert own >= 0.0
            # every scope directly beneath a piece has a name here, and
            # each is the sum of what its series observed meanwhile
            assert set(parts) <= set(PART_SERIES) | {"state_write"}
            for part, hists in PART_SERIES.items():
                assert parts.get(part, 0.0) == pytest.approx(
                    sum(delta[h] for h in hists), abs=1e-3), part
        # parts and own time are the piece's length, as the series have
        # them: to a microsecond over both pieces
        total = obs.get("sim_piece_ms").sum
        assert total == pytest.approx(
            obs.get("sim_piece_own_ms").sum
            + sum(sum(e["args"]["parts"].values()) for e in pieces),
            abs=1e-3)
        assert total == pytest.approx(
            sum(e["dur"] for e in pieces) * 1e-3, abs=0.5)
        # the loop idles between pieces and never inside one
        assert seen >= set(PART_SERIES) and "node_idle" not in seen
        assert obs.get("sim_node_idle_ms").count == len(
            [e for e in rec._ring if e["name"] == "node_idle"]) >= 2
        # every series of a piece has observed, the frame among them
        # (5 Hz by the wall clock, whoever listens)
        for h in ("sim_dispatch_ms", "sim_frame_ms", "sim_node_idle_ms",
                  "sim_node_poll_ms", "sim_pipeline_empty_ms"):
            assert obs.get(h).count > 0, h
        ndisp = sum(e["name"] == "chunk_dispatch" for e in rec._ring)
        assert obs.get("sim_dispatch_ms").count == ndisp \
            == obs.get("sim_pipeline_empty_ms").count
        polls = [e for e in rec._ring if e["name"] == "node_poll"]
        assert polls and {e["cat"] for e in polls} == {"node"}
        assert obs.get("sim_node_poll_ms").count == len(polls)
        # a poll lies in a piece while OP and in the idle stretch
        # between two pieces: the wait that stretch is made of
        by_id = {e["id"]: e["name"] for e in rec._ring if e["ph"] == "X"}
        assert {by_id.get(e["parent"]) for e in polls} \
            == {"piece", "node_idle"}

    def test_a_slow_piece_keeps_its_account(self, monkeypatch, capsys):
        from bluesky_tpu.simulation.simnode import DetachedSimNode
        rec = get_recorder()
        node = DetachedSimNode(nmax=16)
        obs = node.sim.obs
        _run_pieces(node, [f"CASE_{i}" for i in range(8)], hold_at=1.0)
        assert obs.get("sim_piece_ms").count == 8
        assert obs.get("sim_piece_slow").value == 0
        assert len(rec) == 0                   # the recorder was off
        assert "slow piece" not in capsys.readouterr().out
        nap = 2.6 * obs.get("sim_piece_ms").percentile(0.5) * 1e-3
        reset = node.sim.reset
        monkeypatch.setattr(node.sim, "reset",
                            lambda: (time.sleep(nap), reset())[1])
        _run_pieces(node, ["CASE_SLOW"], hold_at=1.0)
        assert obs.get("sim_piece_slow").value == 1
        line, = [ln for ln in capsys.readouterr().out.splitlines()
                 if "slow piece" in ln]
        assert "slow piece CASE_SLOW:" in line
        assert line.split("): ")[1].startswith("piece_reset ")
        assert len(rec) == 0
        # with the recorder on, the same account is an instant
        rec.enable()
        _run_pieces(node, ["CASE_SLOW2"], hold_at=1.0)
        slow, = [e for e in rec._ring if e["name"] == "piece_slow"]
        assert slow["args"]["piece"] == "CASE_SLOW2"
        assert next(iter(slow["args"]["parts"])) == "piece_reset"
        assert obs.get("sim_piece_slow").value == 2

    def test_a_pack_is_one_piece(self):
        from bluesky_tpu.simulation.simnode import DetachedSimNode
        rec = get_recorder()
        rec.clear()
        rec.enable()
        node = DetachedSimNode(nmax=16)
        obs = node.sim.obs
        node.event(b"BATCH", {"worlds": [_piece("W_A"), _piece("W_B")]},
                   [])
        lead = node.worlds.sims[0]
        for _ in range(400):
            node.step()
            if node.worlds is None:
                break
        assert node.worlds is None and node._piece_span is None
        spans = [e for e in rec._ring if e["ph"] == "X"]
        by_id = {e["id"]: e for e in spans}
        piece, = [e for e in spans if e["name"] == "piece"]
        assert piece["args"]["piece"] == "W_A"
        assert piece["args"]["worlds"] == 2
        for name in ("piece_reset", "pack_build"):
            sp, = [e for e in spans if e["name"] == name]
            assert sp["parent"] == piece["id"]
        # the worlds' own scopes book with the worker's piece
        assert {"piece_reset", "pack_build", "chunk_dispatch",
                "chunk_edge"} <= set(piece["args"]["parts"])
        joint = [e for e in spans if e["name"] == "chunk_dispatch"
                 and e["cat"] == "worlds"]
        assert joint and all(by_id[e["parent"]] is piece for e in joint)
        assert lead.obs.get("sim_dispatch_ms").count == len(joint) \
            == lead.obs.get("sim_pipeline_empty_ms").count
        # what the worlds observed ships with the worker's registry
        # once the pack is gone
        assert obs.get("sim_dispatch_ms").count == len(joint)
        assert obs.get("sim_device_wait_ms").count == sum(
            e["name"] == "chunk_edge" for e in spans)
        for h in ("sim_piece_ms", "sim_piece_own_ms", "sim_pack_build_ms",
                  "sim_piece_reset_ms"):
            assert obs.get(h).count == 1, h
        assert obs.get("sim_piece_ms").sum == pytest.approx(
            obs.get("sim_piece_own_ms").sum
            + sum(piece["args"]["parts"].values()), abs=1e-3)
        # the turnaround is stamped, and the next BATCH of either kind
        # observes it; that piece's first dispatch closes the stretch
        # the pack's last retirement opened
        assert obs.get("sim_piece_turnaround_ms").count == 0
        assert node.sim._t_drained is not None
        node.step()
        _run_pieces(node, ["CASE_C"])
        assert obs.get("sim_piece_turnaround_ms").count == 1
        assert obs.get("sim_piece_ms").count == 2
        assert obs.get("sim_pipeline_empty_ms").sum > 0.0


class TestPipelineEmpty:
    @staticmethod
    def _ready(sim):
        for i in range(2):
            do(sim, f"CRE KL{i} B744 {52 + i} {4 + i} 90 FL200 250")
        sim.op()
        sim.fastforward()            # no pacing sleep between chunks

    @pytest.mark.parametrize("path", ["pipelined", "sync", "stacked"])
    def test_zero_behind_a_chunk_and_the_stretch_after_a_drain(
            self, path):
        if path == "stacked":
            from bluesky_tpu.simulation.worlds import WorldBatch
            clock = time.perf_counter
            wb = WorldBatch([(p["scentime"], p["scencmd"]) for p in
                             (_piece("W_A", 9.0), _piece("W_B", 9.0))],
                            simkw={"nmax": 16},
                            drained_at=clock() - 0.05)
            sim, turn = wb.sims[0], wb.step
            other = wb.sims[1].obs.get("sim_pipeline_empty_ms")
        else:
            sim = Simulation(nmax=16)
            sim.pipeline_enabled = path == "pipelined"
            self._ready(sim)
            turn, other = sim.step, None
        h = sim.obs.get("sim_pipeline_empty_ms")
        while h.count < 3:
            turn()
        if path == "pipelined":
            # a first dispatch has no retirement to count from; the
            # later ones are behind the chunk before them
            assert sim._inflight and h.sum == 0.0
            sim.drain_pipeline()
        elif path == "sync":
            # every chunk is retired before the next is dispatched:
            # the first has nothing to count from, the rest are short
            assert 0.0 < h.sum < 50.0
        else:
            # the first dispatch closes the stretch its owner handed
            # over; one observation a joint dispatch, in the first
            # world's registry
            assert 50.0 <= h.sum < 5000.0
            assert other.count == 0
            assert wb.stats["joint_dispatches"] == h.count
        assert sim._t_drained is not None
        before, n = h.sum, h.count
        time.sleep(0.03)
        turn()
        assert h.count == n + 1
        assert 30.0 <= h.sum - before < 1000.0


class TestRecorderLeavesTheProgramAlone:
    def _run(self, monkeypatch, mode, tmp_path):
        """Step a fresh sim four chunks with the recorder off / on /
        inside a PROFILE DEVICE window (profiler stubbed): the lowered
        text of its first chunk program, the stepped state, the order
        in which chunks were dispatched and retired, and the numbers of
        fences and of ``device_get`` pulls."""
        with monkeypatch.context() as m:
            return self._run_patched(m, mode, tmp_path)

    def _run_patched(self, m, mode, tmp_path):
        import jax
        from bluesky_tpu.core import step as stepmod
        rec = get_recorder()
        rec.clear()
        rec.enable(mode != "off")
        real = stepmod.run_steps_edge
        texts, order, fences = [], [], []

        def spy(state, cfg, nsteps, **kw):
            if not texts:
                texts.append(real.lower(state, cfg, nsteps, **kw).as_text())
            return real(state, cfg, nsteps, **kw)
        m.setattr(stepmod, "run_steps_edge", spy)
        m.setattr(jax.profiler, "start_trace", lambda d: None)
        m.setattr(jax.profiler, "stop_trace", lambda: None)
        real_block, real_get = jax.block_until_ready, jax.device_get
        m.setattr(jax, "block_until_ready",
                  lambda x: (fences.append(1), real_block(x))[1])
        pulls = []
        m.setattr(jax, "device_get",
                  lambda x: (pulls.append(1), real_get(x))[1])
        sim = Simulation(nmax=16)
        for fn in ("_dispatch_chunk", "_finish_edge"):
            def wrap(*a, _fn=fn, _real=getattr(sim, fn), **kw):
                order.append((_fn, sim._chunk_seq))
                return _real(*a, **kw)
            m.setattr(sim, fn, wrap)
        for i in range(3):
            do(sim, f"CRE KL{i} B744 {52 + i} {4 + i} 90 FL200 250")
        if mode == "window":
            do(sim, f"PROFILE DEVICE 2 {tmp_path / 'devprof'}")
        sim.op()
        for _ in range(4):
            sim.step()
        sim.drain_pipeline()
        sim.devprof.abort_window()
        return (texts[0], state_hash(sim), order,
                (len(fences), len(pulls)), sim)

    def test_program_state_and_order_do_not_depend_on_the_recorder(
            self, monkeypatch, tmp_path):
        off = self._run(monkeypatch, "off", tmp_path)
        on = self._run(monkeypatch, "on", tmp_path)
        win = self._run(monkeypatch, "window", tmp_path)
        assert off[0] == on[0] == win[0]          # lowered text
        assert off[1] == on[1] == win[1]          # stepped state
        # no fence: a windowed run dispatches chunk k+1 before it
        # retires chunk k, exactly as an unwindowed one does
        assert off[2] == on[2] == win[2]
        assert ("_dispatch_chunk", 1) in off[2] and off[2].index(
            ("_finish_edge", 2)) > off[2].index(("_dispatch_chunk", 1))
        # no block_until_ready anywhere, and the same device->host pulls
        assert off[3] == on[3] == win[3] and off[3][0] == 0
        assert len(win[4].devprof.windows) == 1
        assert len(win[4].devprof.windows[0]["chunks"]) == 2


# ------------------------------------------------------ fleet aggregation
class TestFleetAggregation:
    def test_worker_deltas_reach_the_server(self):
        zmq = pytest.importorskip("zmq")  # noqa: F841
        from bluesky_tpu.network.client import Client
        from bluesky_tpu.network.server import Server
        from bluesky_tpu.simulation.simnode import SimNode
        from tests.test_network import free_ports, wait_for

        ev, st, wev, wst = free_ports(4)
        server = Server(headless=True,
                        ports=dict(event=ev, stream=st, wevent=wev,
                                   wstream=wst),
                        spawn_workers=False, hb_interval=0.2)
        server.start()
        time.sleep(0.2)
        node = SimNode(event_port=wev, stream_port=wst, nmax=16)
        thread = threading.Thread(target=node.run, daemon=True)
        thread.start()
        client = Client()
        try:
            client.connect(event_port=ev, stream_port=st, timeout=5.0)
            assert wait_for(lambda: (client.receive(10),
                                     len(client.nodes) >= 1)[1])
            client.stack("CRE KL1 B744 52 4 90 FL200 250")
            client.stack("OP")
            # worker heartbeats piggyback obs deltas; the server merges
            # them into its fleet registry
            assert wait_for(
                lambda: "sim_chunk_latency_ms" in server.fleet.snapshot(),
                timeout=30)
            fleet_lat = server.fleet.get("sim_chunk_latency_ms")
            assert fleet_lat.count > 0
            # METRICS round-trip: broker + fleet registries to a client
            client.request_metrics()
            assert wait_for(lambda: (client.receive(10),
                                     client.last_metrics is not None)[1],
                            timeout=10)
            m = client.last_metrics
            assert "server" in m and "fleet" in m
            assert "sim_chunk_latency_ms" in m["fleet"]
            assert "server_queue_depth" in m["server"]
            assert "== server ==" in m["text"]
        finally:
            node.quit()
            thread.join(timeout=5)
            server.stop()
            server.join(timeout=5)
            client.close()
