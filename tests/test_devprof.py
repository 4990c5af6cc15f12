"""Device observability + perf sentinel (ISSUE-12): compile-cache
accounting, memory watermarks, PROFILE DEVICE trace windows with
per-chunk attribution, and the BENCH_HISTORY regression comparer.

Contracts pinned here:

* Compile telemetry — a dispatch key is counted as a cache miss
  exactly ONCE; an off-ladder CHUNKSTEPS value lands in the
  off-ladder counter (mid-run recompile) while ladder rungs count as
  warm-up; repeat dispatches are hits.  HEALTH surfaces the split.
* Memory watermarks — forced samples set per-device live/peak gauges
  from jax.live_arrays; peak is monotone; the unforced path is a
  no-op with devprof_mem_dt=0 (the obs-off contract).
* PROFILE DEVICE — a window over n chunks on the 8-device mesh
  leaves the XLA trace tree on disk with the host's spans in it as
  ``bs/`` annotations, ``<dir>_spans.json`` beside it (the window's
  spans, ring off included, and each chunk's host stamps), a
  device_profile span + n devprof_chunk attribution events, and
  scripts/devprof_report.py prints the pinned
  seq/chunk/compute_ms/halo_ms/edge_ms table from either.  The window
  fences nothing, and the time inside the profiler's start and stop
  is the profile_start/profile_stop spans and in no histogram.
* Perf sentinel — bench_history.compare flags an injected ~2x
  slowdown against a doctored baseline (exit 1, structured report
  naming the regressed row) and stays quiet within threshold;
  write_bench_json appends provenance-tagged history lines except
  when history=False (reprojection round-trips).
"""
import glob
import gzip
import json
import os
import sys

import pytest

from bluesky_tpu import settings
from bluesky_tpu.obs.trace import get_recorder
from bluesky_tpu.simulation.sim import Simulation

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "scripts"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


@pytest.fixture()
def sim():
    return Simulation(nmax=16)


@pytest.fixture(autouse=True)
def _recorder_reset():
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


# -------------------------------------------------------- compile telemetry
class TestCompileTelemetry:
    def test_offladder_chunksteps_misses_exactly_once(self, sim):
        """CHUNKSTEPS 7 is not a CHUNK_LADDER rung: the first dispatch
        at that shape is ONE off-ladder cache miss; every further
        chunk at the same key is a hit, never a second miss."""
        assert 7 not in Simulation.CHUNK_LADDER
        _fleet(sim)
        do(sim, "CHUNKSTEPS 7")
        sim.op()
        off = sim.obs.counter("devprof_cache_misses_offladder")
        sim.run(until_simt=sim.simt + 14 * sim.simdt)   # 2 full chunks
        assert off.value == 1
        hits0 = sim.obs.counter("devprof_cache_hits").value
        assert hits0 >= 1
        sim.run(until_simt=sim.simt + 14 * sim.simdt)   # same key again
        assert off.value == 1                           # STILL one
        assert sim.obs.counter("devprof_cache_hits").value > hits0
        # the off-ladder miss also left a recorder-visible summary
        assert "off-ladder 1" in sim.devprof.compile_summary()

    def test_ladder_chunks_count_as_warmup_not_offladder(self, sim):
        _fleet(sim)
        sim.op()
        sim.run(until_simt=sim.simt + 2 * sim.chunk_steps * sim.simdt)
        assert sim.chunk_steps in Simulation.CHUNK_LADDER
        assert sim.obs.counter("devprof_cache_misses_ladder").value >= 1
        assert sim.obs.counter(
            "devprof_cache_misses_offladder").value == 0

    def test_compile_listener_observes_real_compiles(self, sim):
        """A fresh jit program fires the jax.monitoring duration
        events into every subscribed registry."""
        import jax
        import jax.numpy as jnp
        jax.block_until_ready(
            jax.jit(lambda x: x * 1.0009765625)(jnp.ones(3)))
        h = sim.obs.get("devprof_compile_backend_ms")
        assert h is not None and h.count >= 1
        assert sim.obs.get("devprof_backend_compiles").value >= 1

    def test_health_reports_the_compile_split(self, sim):
        _fleet(sim)
        sim.op()
        sim.run(until_simt=sim.simt + sim.chunk_steps * sim.simdt)
        out = do(sim, "HEALTH")
        assert "compiles: ladder warm-up" in out
        assert "off-ladder" in out

    def test_telemetry_knob_disables_accounting(self, sim, monkeypatch):
        monkeypatch.setattr(settings, "devprof_compile_telemetry",
                            False)
        sim.devprof.note_dispatch("edge", 7, 16, 1)
        assert sim.obs.counter(
            "devprof_cache_misses_offladder").value == 0


# -------------------------------------------------------- memory watermarks
class TestMemoryWatermarks:
    def test_forced_sample_sets_gauges_and_peak(self, sim):
        _fleet(sim)
        sim.op()
        sim.run(until_simt=sim.simt + sim.simdt)
        per = sim.devprof.sample_memory(force=True)
        assert per and sum(per.values()) > 0
        wm = sim.devprof.watermarks()
        assert wm
        for live, peak in wm.values():
            assert peak >= live >= 0
        total = sim.obs.get("devprof_live_bytes_total")
        assert total.value == sum(per.values())

    def test_unforced_sample_is_noop_with_dt_zero(self, sim):
        assert settings.devprof_mem_dt == 0.0
        assert sim.devprof.sample_memory() is None
        assert sim.obs.get("devprof_live_bytes_total") is None

    def test_throttle_honors_mem_dt(self, sim, monkeypatch):
        monkeypatch.setattr(settings, "devprof_mem_dt", 100.0)
        assert sim.devprof.sample_memory(now=0.0) is not None
        assert sim.devprof.sample_memory(now=50.0) is None   # inside dt
        assert sim.devprof.sample_memory(now=150.0) is not None

    def test_donation_check_counts_live_leaves(self, sim, monkeypatch):
        import jax.numpy as jnp
        state = {"a": jnp.ones(8), "b": jnp.zeros(4)}
        assert sim.devprof.check_donation(state) == 0    # knob off
        monkeypatch.setattr(settings, "devprof_donation_check", True)
        missed = sim.devprof.check_donation(state)
        assert missed == 2                   # neither buffer was donated
        assert sim.obs.counter("devprof_donation_missed").value == 2


# ------------------------------------------------------- PROFILE DEVICE
class TestProfileDeviceWindow:
    def test_window_on_8dev_mesh_traces_and_attributes(
            self, sim, tmp_path, monkeypatch, capsys):
        """The acceptance walk: PROFILE DEVICE on the 8-device CPU
        mesh -> XLA trace on disk with the host's spans in it, the
        spans file beside it, devprof_chunk attribution events and
        devprof_report.py's pinned table."""
        monkeypatch.setattr(settings, "trace_dir", str(tmp_path))
        rec = get_recorder()
        rec.clear()
        rec.enable()
        _fleet(sim)
        do(sim, "SHARD REPLICATE 8")
        # warm the sharded program up OUTSIDE the window so the trace
        # captures execution, not the multi-second XLA compile (which
        # would bloat the trace file by orders of magnitude)
        sim.op()
        sim.run(until_simt=sim.simt + 2 * sim.chunk_steps * sim.simdt)
        sim.drain_pipeline()
        devdir = str(tmp_path / "devprof")
        out = do(sim, f"PROFILE DEVICE 2 {devdir}")
        assert "2 chunk" in out and devdir in out
        try:
            sim.run(until_simt=sim.simt
                    + 4 * sim.chunk_steps * sim.simdt)
            sim.drain_pipeline()
        finally:
            sim.devprof.abort_window()       # never leak a jax trace
        assert not sim.devprof.window_active
        assert len(sim.devprof.windows) == 1
        win = sim.devprof.windows[0]
        assert win["n_chunks"] == 2 and len(win["chunks"]) == 2

        # the XLA trace tree landed under the requested dir
        traces = glob.glob(os.path.join(
            devdir, "plugins", "profile", "*", "*.trace.json*"))
        assert traces, "jax.profiler left no trace files"

        # ring: one device_profile span + two devprof_chunk events
        names = [e["name"] for e in rec._ring]
        assert names.count("device_profile") == 1
        chunks = [e for e in rec._ring if e["name"] == "devprof_chunk"]
        assert len(chunks) == 2
        for ev in chunks:
            for k in ("seq", "chunk", "compute_ms", "halo_ms",
                      "edge_ms"):
                assert k in ev["args"], f"devprof_chunk missing {k}"
        prof = next(e for e in rec._ring
                    if e["name"] == "device_profile")
        assert prof["args"]["dir"] == devdir
        assert prof["args"]["n_chunks"] == 2

        # the always-on series took the windowed chunks like any other
        for h in ("sim_device_wait_ms", "sim_edge_work_ms"):
            assert sim.obs.get(h).count \
                == sim.obs.get("sim_chunk_latency_ms").count
        for h in ("devprof_compute_ms", "devprof_halo_ms",
                  "devprof_edge_ms"):
            assert sim.obs.get(h) is None

        # the window's spans, beside the profiler's directory
        assert win["spans_file"] == devdir + "_spans.json"
        doc = json.loads(open(win["spans_file"]).read())
        assert doc["dir"] == devdir and doc["n_chunks"] == 2
        assert [c["seq"] for c in doc["chunks"]] \
            == sorted(c["args"]["seq"] for c in chunks)
        for c in doc["chunks"]:
            assert c["dispatch_start_us"] <= c["enqueue_us"] \
                <= c["dispatch_end_us"] <= c["wait_end_us"]
        names = {e["name"] for e in doc["spans"]}
        assert {"profile_start", "profile_stop", "chunk_dispatch",
                "chunk_edge", "device_wait", "devprof_chunk"} <= names
        stop = next(e for e in doc["spans"]
                    if e["name"] == "profile_stop")
        assert stop["parent"] is None      # after chunk_edge has shut

        # the host's spans are in the profiler's own file, on its clock
        with gzip.open(next(t for t in traces if t.endswith(".gz")),
                       "rt") as f:
            xla = json.load(f)["traceEvents"]
        bs = {e["name"] for e in xla
              if str(e.get("name", "")).startswith("bs/")}
        assert {"bs/chunk_edge", "bs/device_wait", "bs/clock"} <= bs
        # ... and the offset the worker read back from its bs/clock
        # mark puts each span's own stamp on its annotation
        zero = doc["profiler_zero_us"]
        twins = sorted((e["ts"] for e in xla
                        if e.get("name") == "bs/device_wait"))
        mine = sorted(e["ts"] for e in doc["spans"]
                      if e["name"] == "device_wait")
        assert len(twins) == len(mine) >= 2
        for t_prof, t_host in zip(twins, mine):
            assert abs(zero + t_prof - t_host) < 500.0      # us

        # devprof_report: the pinned table, from the spans file and
        # from a ring dump alike
        dump = rec.dump(str(tmp_path / "host.json"))
        import devprof_report
        import trace_report
        for src in (win["spans_file"], dump):
            assert devprof_report.main([src]) == 0
            captured = capsys.readouterr().out
            assert "compute_ms" in captured and "halo_ms" in captured
            rows = devprof_report.attribution_rows(
                trace_report.load([src]))
            assert len(rows) == 2
            assert list(rows[0]) == ["seq", "chunk", "compute_ms",
                                     "halo_ms", "edge_ms"]

    def test_window_records_spans_with_the_ring_off(self, sim, tmp_path,
                                                    monkeypatch):
        """TRACE OFF: a window still writes its spans (the benchmark's
        traced run never turns the recorder on), and leaves the ring
        empty; outside a window the recorder is the shared no-op."""
        import jax
        monkeypatch.setattr(jax.profiler, "start_trace", lambda d: None)
        monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
        rec = get_recorder()
        assert not rec.enabled
        _fleet(sim)
        devdir = str(tmp_path / "w")
        do(sim, f"PROFILE DEVICE 2 {devdir}")
        sim.op()
        sim.run(until_simt=sim.simt + 4 * sim.chunk_steps * sim.simdt)
        sim.drain_pipeline()
        assert len(sim.devprof.windows) == 1 and len(rec) == 0
        assert not rec.active
        doc = json.loads(open(devdir + "_spans.json").read())
        assert len(doc["chunks"]) == 2
        got = [e["name"] for e in doc["spans"]]
        assert got.count("chunk_edge") >= 2 and "profile_stop" in got
        # the chunk_dispatch that opened the window is in it too
        assert doc["chunks"][0]["seq"] in [
            e["args"]["seq"] for e in doc["spans"]
            if e["name"] == "chunk_dispatch"]

    def test_slow_profiler_is_in_its_spans_and_in_no_histogram(
            self, sim, tmp_path, monkeypatch):
        """A stop_trace that takes 0.4 s (on the chip it takes far
        longer) shows in profile_stop and in none of the wall-time
        series: they run on the program's clock, which stops inside
        the profiler."""
        import time
        import jax
        monkeypatch.setattr(jax.profiler, "start_trace",
                            lambda d: time.sleep(0.2))
        monkeypatch.setattr(jax.profiler, "stop_trace",
                            lambda: time.sleep(0.4))
        _fleet(sim)
        do(sim, "DTMULT 10")                 # no wall-clock pacing
        sim.op()
        sim.run(until_simt=sim.simt + 2 * sim.chunk_steps * sim.simdt)
        sim.drain_pipeline()                 # compiled, warm
        series = ("sim_chunk_latency_ms", "sim_device_wait_ms",
                  "sim_edge_work_ms", "sim_stack_ms")
        before = {h: sim.obs.get(h).sum for h in series}
        devdir = str(tmp_path / "w")
        do(sim, f"PROFILE DEVICE 2 {devdir}")
        sim.run(until_simt=sim.simt + 5 * sim.chunk_steps * sim.simdt)
        sim.drain_pipeline()
        assert len(sim.devprof.windows) == 1
        assert sim.devprof.profiler_s >= 0.6
        doc = json.loads(open(devdir + "_spans.json").read())
        dur = {e["name"]: e["dur"] for e in doc["spans"]}
        assert dur["profile_start"] >= 0.2e6
        assert dur["profile_stop"] >= 0.4e6
        for h in series:
            assert sim.obs.get(h).sum - before[h] < 150.0, h

    def test_second_window_request_refused_while_active(self, sim,
                                                        tmp_path,
                                                        monkeypatch):
        monkeypatch.setattr(settings, "trace_dir", str(tmp_path))
        _fleet(sim)
        do(sim, f"PROFILE DEVICE 3 {tmp_path / 'd'}")
        try:
            sim.op()
            sim.run(until_simt=sim.simt + sim.simdt)   # opens window
            assert sim.devprof.window_active
            out = do(sim, "PROFILE DEVICE")
            assert "active" in out.lower()
        finally:
            sim.devprof.abort_window()

    def test_profile_device_rejects_bad_count(self, sim):
        assert "need" in do(sim, "PROFILE DEVICE 0").lower()

    def test_window_off_path_changes_nothing(self, sim):
        """No armed window: begin_chunk reports False and note hooks
        are no-ops — the always-on path stays attribute checks."""
        assert sim.devprof.begin_chunk(1) is False
        sim.devprof.note_chunk(1, 20, 1.0, 1.1, 1.2, 0.5)
        sim.devprof.note_edge(1, 1.3, 0.2)
        assert sim.devprof.end_window() is None
        assert sim.devprof.windows == []
        assert not get_recorder().active


# ------------------------------------------------------- bench history
def _hist_line(series, ts, row, platform="cpu:cpu", rev="aaaa111"):
    return json.dumps({"series": series, "ts": ts, "git_rev": rev,
                       "platform": platform, "row": row},
                      sort_keys=True)


class TestBenchHistorySentinel:
    IDENT = {"n": 100, "backend": "dense", "geometry": "regional"}

    def _write(self, path, rates):
        with open(path, "w") as f:
            for i, r in enumerate(rates):
                row = dict(self.IDENT, ac_steps_per_s=r)
                f.write(_hist_line("BENCH_X", float(i), row) + "\n")

    def test_injected_2x_slowdown_fails_with_named_row(self, tmp_path,
                                                       capsys):
        import bench_history
        hist = str(tmp_path / "h.jsonl")
        rpt = str(tmp_path / "r.json")
        self._write(hist, [100.0, 102.0, 98.0, 49.0])   # ~2x slower
        rc = bench_history.main(["compare", hist, "--report", rpt])
        assert rc == 1
        report = json.loads(open(rpt).read())
        assert report["checked_groups"] == 1
        (reg,) = report["regressions"]
        assert reg["series"] == "BENCH_X"
        assert reg["metric"] == "ac_steps_per_s"
        assert reg["identity"]["n"] == 100
        assert reg["baseline"] == 100.0 and reg["newest"] == 49.0
        assert reg["change_pct"] == -51.0
        assert reg["baseline_runs"] == 3
        err = capsys.readouterr().err
        assert "PERF REGRESSION" in err and "BENCH_X" in err

    def test_within_threshold_and_direction_aware(self, tmp_path):
        import bench_history
        hist = str(tmp_path / "h.jsonl")
        # 5% down: inside the 10% gate
        self._write(hist, [100.0, 100.0, 95.0])
        assert bench_history.main(["compare", hist]) == 0
        # overhead_pct DROPPING is an improvement, never a regression
        with open(hist, "w") as f:
            for i, o in enumerate((4.0, 4.2, 0.5)):
                f.write(_hist_line(
                    "BENCH_OBS", float(i),
                    {"scenario": "s", "overhead_pct": o}) + "\n")
        assert bench_history.main(["compare", hist]) == 0
        # ...but overhead RISING past the gate is one
        with open(hist, "a") as f:
            f.write(_hist_line("BENCH_OBS", 9.0,
                               {"scenario": "s",
                                "overhead_pct": 9.0}) + "\n")
        assert bench_history.main(["compare", hist]) == 1

    def test_absent_or_torn_history_never_blocks(self, tmp_path,
                                                 capsys):
        import bench_history
        assert bench_history.main(
            ["compare", str(tmp_path / "missing.jsonl")]) == 0
        hist = str(tmp_path / "h.jsonl")
        with open(hist, "w") as f:
            f.write("{torn line\n")
            f.write(_hist_line("BENCH_X", 1.0,
                               dict(self.IDENT,
                                    ac_steps_per_s=50.0)) + "\n")
        assert bench_history.main(["compare", hist]) == 0  # 1 run only
        assert "unparseable" in capsys.readouterr().err

    def test_write_bench_json_appends_provenance(self, tmp_path,
                                                 monkeypatch):
        import bench
        hist = str(tmp_path / "hist.jsonl")
        monkeypatch.setattr(settings, "bench_history_path", hist)
        out = str(tmp_path / "BENCH_X.json")
        rows = [{"n": 5, "ac_steps_per_s": 10.0},
                {"n": 9, "projected": True},
                {"n": 7, "failed": "oom"}]
        bench.write_bench_json(out, rows)
        lines = [json.loads(l) for l in open(hist)]
        assert len(lines) == 1                 # measured rows only
        e = lines[0]
        assert e["series"] == "BENCH_X"
        assert e["row"]["n"] == 5
        assert e["platform"] == e["row"]["platform"]
        assert e["git_rev"] and e["ts"] > 0
        # reprojection round-trips must NOT re-append
        bench.write_bench_json(out, rows, history=False)
        assert len(open(hist).readlines()) == 1
        # the JSON itself round-trips through the shared shape
        doc = json.loads(open(out).read())
        assert doc["rows"][0]["n"] == 5
