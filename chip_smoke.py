#!/usr/bin/env python3
"""The quickest proof that the served path still starts on the chip.

    python chip_smoke.py              one chip, ~10 minutes cold
    python chip_smoke.py --devices 4  one four-chip host (SHARD modes)
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearsal
                                      toy sizes on the CPU, to debug the
                                      script itself; proves nothing

The deployment is the one the repository has always measured
(BASELINE.json config 3 at the top of its sweep): N=100,000 aircraft over
the continental box 35-60N, -10..30E, CDMETHOD SPARSE, RESO MVP, simdt
0.05 s, CD at 1 Hz with 300 s look-ahead, 5 nm / 1000 ft, float32.

This process never imports JAX: a parent that has touched JAX holds the
chip, and a child that needs it then fails or hangs.  Each phase is a
child process, run one after another, so the chip has one owner at a
time:

  served   a broker started as ``python -m bluesky_tpu --headless``, the
           one worker it spawns, and a network Client: the fleet is made
           with stack commands, a few seconds of OP at the default
           20-step chunk with ACDATA and SIMINFO subscribers, FF for
           1000-step chunks, POS / HEALTH / METRICS answered
  warm     the served configuration's first chunk again in a fresh
           process: what the persistent compile cache gives back
  kernels  an embedded Simulation at N=16,384: one CD interval under
           PALLAS and SPARSE with each resolver against DENSE (ops/cd.py,
           the plain reference), compiled by Mosaic; then the three
           chunk programs that are off by default, compiled once

It fails (exit code 1, no result line) if any phase fails, and when JAX
finds no accelerator.  On success the last line of standard output is
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
with the device as JAX reports it.  The numbers it prints are readings
for CHANGES.md, each with its unit and device; they are not claims.
"""
import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))
SEED = 0
LAT0, LAT1, LON0, LON1 = 35.0, 60.0, -10.0, 30.0
TILE_DEG = 5.0                     # MCRE fills the (square) view
SIMDT = 0.05
#: (N served, N kernels) at the real size and for the CPU rehearsal
SIZES = {False: (100_000, 16_384), True: (2_000, 512)}
PHASES_1 = ("served", "warm", "kernels")
PHASES_4 = ("shard",)
#: wall-clock limit of each child [s].  The whole run has 1200 s; on a
#: v5e host the three phases took 146 + 46 + 431 s cold (PR 21).
PHASE_TIMEOUT = {"served": 600, "warm": 300, "kernels": 900,
                 "shard": 2000}
RESULT = "CHIP_SMOKE_RESULT "      # a child's last line: RESULT + json


class SmokeFailure(Exception):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)
    print(f"  ok: {msg}", flush=True)


def command_error(echo):
    """The stack's three ways to say a command did not run."""
    return (" failed: " in echo or echo.startswith("Unknown command")
            or echo.startswith("Usage:"))


def fleet_commands(n):
    """Stack commands that create n aircraft uniformly over the box:
    MCRE draws over the view, and a view is a square of degrees, so the
    box is covered by 5-degree views with n split evenly over them."""
    nlat = int(round((LAT1 - LAT0) / TILE_DEG))
    nlon = int(round((LON1 - LON0) / TILE_DEG))
    ntiles = nlat * nlon
    cmds = [f"SEED {SEED}"]
    for k in range(ntiles):
        i, j = divmod(k, nlon)
        cnt = n // ntiles + (1 if k < n % ntiles else 0)
        if cnt:
            cmds += [f"PAN {LAT0 + (i + 0.5) * TILE_DEG} "
                     f"{LON0 + (j + 0.5) * TILE_DEG}",
                     f"ZOOM {2.0 / TILE_DEG}", f"MCRE {cnt}"]
    return cmds


def dev_tag(dev):
    return f"[{dev['platform']} {dev['device_kind']} x{dev['count']}]"


def require_device(dev, rehearsal, ndev=1):
    """The one rule every phase applies to the device it found."""
    if dev["platform"] == "cpu" and not rehearsal:
        raise SmokeFailure(
            f"JAX found no accelerator: platform {dev['platform']!r}, "
            f"device_kind {dev['device_kind']!r}, {dev['count']} "
            "device(s).  This script proves the chip path; "
            "--rehearsal runs toy sizes on the CPU")
    if dev["count"] < ndev:
        raise SmokeFailure(f"--devices {ndev} asked, {dev['count']} "
                           f"visible {dev_tag(dev)}")
    if rehearsal:
        print(f"  REHEARSAL on {dev_tag(dev)}: toy sizes, no device "
              "reading below means anything", flush=True)


# ===================================================================
# phase: served  (this child is the CLIENT; it never imports JAX —
# broker and worker are its descendants, the worker owns the chip)
# ===================================================================
class Session:
    """A network Client plus what it has seen."""

    def __init__(self, client):
        self.client = client
        self.echo = []
        self.frames = {b"ACDATA": 0, b"SIMINFO": 0}
        self.acdata = None
        self.siminfo = []          # (arrival stamp, simt)
        self.simstate = None
        client.event_received.connect(self._on_event)
        client.stream_received.connect(self._on_stream)

    def _on_event(self, name, data, sender):
        if name == b"ECHO":
            self.echo.append(str((data or {}).get("text", "")))
        elif name == b"SIMSTATE":
            self.simstate = data

    def _on_stream(self, name, data, sender):
        if name in self.frames:
            self.frames[name] += 1
        if name == b"ACDATA":
            self.acdata = data
        elif name == b"SIMINFO":
            self.siminfo.append((time.perf_counter(), data["simt"]))

    def pump(self, seconds):
        t_end = time.perf_counter() + seconds
        while time.perf_counter() < t_end:
            self.client.receive(20)

    def wait(self, pred, timeout, what):
        """Pump until pred() holds; returns the stamp."""
        t_end = time.perf_counter() + timeout
        while True:
            self.client.receive(10)
            if pred():
                return time.perf_counter()
            if time.perf_counter() > t_end:
                raise SmokeFailure(f"timed out waiting for {what}")

    def wait_state(self, pred, timeout, what):
        """Ask GETSIMSTATE until pred(reply); returns (stamp, reply).
        One request is outstanding at a time, so no reply is stale; the
        worker answers between chunks, so once the sim holds the stamp
        is late by the 50 ms between requests at most."""
        t_end = time.perf_counter() + timeout
        while True:
            self.simstate = None
            self.client.send_event(b"GETSIMSTATE")
            t = self.wait(lambda: self.simstate is not None,
                          t_end - time.perf_counter(), what)
            if pred(self.simstate):
                return t, self.simstate
            self.pump(0.05)

    def command(self, line, expect, timeout=120.0):
        """Send one stack line, return the first new echo containing
        ``expect``."""
        n0 = len(self.echo)
        self.client.stack(line)
        self.wait(lambda: any(expect in e for e in self.echo[n0:]),
                  timeout, f"the echo of {line!r}")
        return next(e for e in self.echo[n0:] if expect in e)


def metric(text, name):
    """One series of a METRICS DUMP echo (Registry.text format)."""
    for ln in text.splitlines():
        if ln.startswith(name + ":"):
            return float(ln.split(":", 1)[1].split()[0])
    return None


def phase_served(rehearsal):
    import bluesky_tpu  # noqa: F401 — names the compile cache for the
    #                     broker and the worker, which inherit it
    from bluesky_tpu.network.client import Client
    if "jax" in sys.modules:
        raise SmokeFailure("the client process imported jax: it would "
                           "hold the chip the worker needs")
    n = SIZES[rehearsal][0]
    op_window = 3.0 if rehearsal else 10.0
    ff_first, ff_steady = 50.0, 200.0          # 1 and 4 chunks of 1000
    work = tempfile.mkdtemp(prefix="chip_smoke_")
    cfgfile = os.path.join(work, "settings.cfg")
    with open(cfgfile, "w") as f:
        f.write(f"nmax = {n}\n"
                "telnet_port = 0\n"
                "devprof_mem_dt = 5.0\n"
                f"log_path = {os.path.join(work, 'output')!r}\n")
    log = open(os.path.join(work, "broker.log"), "w")
    ev, st = 19400, 19401
    broker = subprocess.Popen(
        [sys.executable, "-m", "bluesky_tpu", "--headless",
         "--config-file", cfgfile, "--event-port", str(ev),
         "--stream-port", str(st)],
        cwd=REPO, stdout=log, stderr=subprocess.STDOUT)
    client = Client()
    try:
        client.connect(event_port=ev, stream_port=st, timeout=60.0)
        s = Session(client)
        s.wait(lambda: bool(client.nodes), 180.0,
               "the worker to register with the broker")
        check(len(client.nodes) == 1,
              "the broker spawned one worker for this host")

        # ---- the device, as the worker's REGISTER told the broker
        client.request_health()
        s.wait(lambda: client.last_health is not None, 30.0, "HEALTH")
        workers = client.last_health["workers"]
        dev = next(iter(workers.values())).get("device")
        check(isinstance(dev, dict), "HEALTH carries the worker's device")
        require_device(dev, rehearsal)
        tag = dev_tag(dev)
        print(f"served: worker on platform {dev['platform']}, "
              f"device_kind {dev['device_kind']}, count {dev['count']}",
              flush=True)

        client.subscribe(b"ACDATA")
        client.subscribe(b"SIMINFO")

        # ---- the fleet, by stack commands, held until OP
        t0 = time.perf_counter()
        client.stack("; ".join(["HOLD", "CDMETHOD SPARSE", "RESO MVP"]
                               + fleet_commands(n)))
        t1, sst = s.wait_state(lambda r: r["ntraf"] == n, 600.0,
                               f"ntraf == {n}")
        print(f"served: ntraf == {n}: created by MCRE over "
              f"{LAT0:g}-{LAT1:g}N {LON0:g}..{LON1:g}E in "
              f"{t1 - t0:.1f} s wall {tag}", flush=True)

        # ---- OP at the default 20-step chunk, streams attached
        simt0 = sst["simt"]
        f0 = dict(s.frames)
        t0 = time.perf_counter()
        client.stack("OP")
        t1, sst = s.wait_state(
            lambda r: r["simt"] >= simt0 + 20 * SIMDT - 1e-6, 900.0,
            "the first 20-step chunk")
        print(f"served: first 20-step chunk {t1 - t0:.1f} s after OP "
              f"(compile included) {tag}", flush=True)
        t_first_chunk = t1 - t0
        i0 = len(s.siminfo)
        s.pump(op_window)
        nac = s.frames[b"ACDATA"] - f0[b"ACDATA"]
        nsi = s.frames[b"SIMINFO"] - f0[b"SIMINFO"]
        check(nac >= 1 and len(s.siminfo) - i0 >= 2,
              "ACDATA and SIMINFO frames received")
        (ta, sa), (tb, sb) = s.siminfo[i0], s.siminfo[-1]
        print(f"served: OP at 20-step chunks, between the SIMINFO frames "
              f"of a {op_window:g} s window: {(sb - sa) / (tb - ta):.3f} "
              f"sim-s per wall-s (OP is paced to the wall clock and "
              f"starts behind it after the compile); {nac} ACDATA and "
              f"{nsi} SIMINFO frames received since OP {tag}", flush=True)
        check(len(s.acdata["id"]) == n,
              f"an ACDATA frame carries {n} aircraft")

        # ---- FF: one 1000-step chunk (compiles), then four (steady)
        client.stack("HOLD")
        s.wait_state(lambda r: r["state"] == 1, 120.0, "HOLD")

        def fastforward(sim_s, timeout):
            _, r0 = s.wait_state(lambda r: True, 120.0, "SIMSTATE")
            t0 = time.perf_counter()
            client.stack(f"OP; FF {sim_s:g}")
            t1, r1 = s.wait_state(
                lambda r: r["state"] == 1
                and r["simt"] >= r0["simt"] + sim_s - 0.5 * SIMDT,
                timeout, f"FF {sim_s:g} to finish")
            check(abs(r1["simt"] - r0["simt"] - sim_s) < 1e-2,
                  f"sim time advanced by the {sim_s:g} s asked "
                  f"({r0['simt']:.2f} -> {r1['simt']:.2f})")
            return t1 - t0

        w = fastforward(ff_first, 900.0)
        print(f"served: FF {ff_first:g} s, the first 1000-step chunk: "
              f"{w:.1f} s wall (compile included) {tag}", flush=True)
        m0 = s.command("METRICS DUMP", "sim registry:")
        w = fastforward(ff_steady, 900.0)
        m1 = s.command("METRICS DUMP", "sim registry:")
        nsteps = int(round(ff_steady / SIMDT))
        print(f"served: FF {ff_steady:g} s = {nsteps} steps in "
              f"{nsteps // 1000} chunks of 1000: {w:.2f} s wall, "
              f"{ff_steady / w:.2f} sim-s per wall-s, "
              f"{n * nsteps / w:.4g} aircraft-steps/s {tag}", flush=True)
        c0 = metric(m0, "devprof_backend_compiles")
        c1 = metric(m1, "devprof_backend_compiles")
        check(c0 is not None and c0 == c1,
              f"no backend compilation in the steady FF window "
              f"(devprof_backend_compiles {c0:g} -> {c1:g})")
        check(metric(m1, "sim_guard_trips") == 0, "the guard word is clean")
        peak = metric(m1, "devprof_peak_bytes_dev0")
        print("served: peak device bytes "
              + (f"{peak:.6g} B" if peak else "not reported by the "
                 "backend") + f" (memory_stats peak_bytes_in_use) {tag}",
              flush=True)
        print(f"served: worker persistent compile cache: "
              f"{metric(m1, 'devprof_persistent_cache_hits') or 0:g} "
              f"hits, "
              f"{metric(m1, 'devprof_persistent_cache_misses') or 0:g} "
              f"misses {tag}", flush=True)

        # ---- requests the worker answers
        acid = s.acdata["id"][0]
        pos = s.command(f"POS {acid}", f"Info on {acid}")
        print("served: POS -> " + pos.splitlines()[1], flush=True)
        s.command("HEALTH", "in flight")
        check(s.acdata["nconf_tot"] >= 1,
              f"conflicts detected (ACDATA nconf_tot "
              f"{s.acdata['nconf_tot']}, nconf_cur "
              f"{s.acdata['nconf_cur']})")
        failed = [e for e in s.echo if command_error(e)]
        check(not failed, "no failed command in the echo"
              + (f": {failed[:3]}" if failed else ""))
        check(broker.poll() is None, "the broker is still running")
        return dict(device=dev, first_chunk_s=round(t_first_chunk, 1))
    except SmokeFailure:
        log.flush()
        with open(log.name) as f:
            print("---- broker/worker log (tail) ----\n"
                  + f.read()[-6000:], flush=True)
        raise
    finally:
        client.close()
        # SIGTERM: the broker QUITs its worker and leaves.  Whatever is
        # still there afterwards shares this child's process group,
        # which the parent kills when the phase ends.
        broker.terminate()
        try:
            broker.wait(timeout=15)
        except subprocess.TimeoutExpired:
            broker.kill()
        log.close()


# ===================================================================
# phases with an embedded Simulation (these children own the chip)
# ===================================================================
def embedded_sim(n, rehearsal, ndev=1):
    import bluesky_tpu  # noqa: F401 — before jax: names the compile cache
    from bluesky_tpu import settings
    settings.log_path = tempfile.mkdtemp(prefix="chip_smoke_")
    from bluesky_tpu.obs.devprof import device_info
    from bluesky_tpu.ops import hostgeo
    dev = device_info()
    require_device(dev, rehearsal, ndev)
    print("  host geodesy core: " + ("compiled _cgeo extension"
          if hostgeo.compiled else "NumPy (no _cgeo extension in this "
          "tree, as in a checkout)"), flush=True)
    from bluesky_tpu.simulation.sim import Simulation
    return Simulation(nmax=n), dev


def run_line(sim, line):
    """Stack one line, process it, fail on any command error."""
    n0 = len(sim.scr.echobuf)
    sim.stack.stack(line)
    sim.stack.process()
    bad = [e for e in sim.scr.echobuf[n0:] if command_error(str(e))]
    if bad:
        raise SmokeFailure(f"{line[:60]!r}: {bad[0]}")


def make_fleet(sim, n, *setup):
    run_line(sim, "; ".join(["HOLD"] + list(setup) + fleet_commands(n)))
    if sim.traf.ntraf != n:
        raise SmokeFailure(f"ntraf {sim.traf.ntraf} != {n}")


def counter(sim, name):
    m = sim.obs.get(name)
    return int(m.value) if m is not None else 0


def phase_warm(rehearsal):
    n = SIZES[rehearsal][0]
    sim, dev = embedded_sim(n, rehearsal)
    tag = dev_tag(dev)
    make_fleet(sim, n, "CDMETHOD SPARSE", "RESO MVP")
    t0 = time.perf_counter()
    run_line(sim, "OP")
    sim.step()
    sim.drain_pipeline()
    w = time.perf_counter() - t0
    hits = counter(sim, "devprof_persistent_cache_hits")
    miss = counter(sim, "devprof_persistent_cache_misses")
    print(f"warm: first 20-step chunk {w:.1f} s after OP in a fresh "
          f"process; persistent compile cache "
          f"{os.environ['JAX_COMPILATION_CACHE_DIR']}: {hits} hits, "
          f"{miss} misses (programs under the cache's 1 s threshold are "
          f"never stored and always miss) {tag}", flush=True)
    check(abs(sim.simt - 20 * SIMDT) < 1e-3, "the chunk ran")
    check(hits >= 1, "the chunk program came from the persistent cache")
    return dict(device=dev, first_chunk_s=round(w, 1), hits=hits)


def phase_kernels(rehearsal):
    import numpy as np
    n = SIZES[rehearsal][1]
    sim, dev = embedded_sim(n, rehearsal)
    tag = dev_tag(dev)
    from bluesky_tpu.core.step import run_steps_edge
    from bluesky_tpu.ops import cd_pallas
    if not rehearsal:
        check(cd_pallas.interpret_default(None) is False,
              "Pallas kernels go to Mosaic here, not to the interpreter")

    def one_interval(backend, reso, *extra):
        """RESET, the seeded fleet again, one 20-step chunk = one CD
        interval; returns what detection left in the state."""
        run_line(sim, "RESET")
        make_fleet(sim, n, f"CDMETHOD {backend}", f"RESO {reso}", *extra)
        ac = sim.traf.state.ac
        key = (np.asarray(ac.lat).tobytes(), np.asarray(ac.trk).tobytes())
        run_line(sim, "OP")
        sim.step()
        sim.drain_pipeline()
        st = sim.traf.state
        check(abs(sim.simt - 20 * SIMDT) < 1e-3
              and not sim.guard.trips
              and bool(np.isfinite(np.asarray(st.ac.lat)).all()),
              f"{backend} {reso} {' '.join(extra)}: one chunk ran, "
              "guard clean, state finite")
        return key, (np.asarray(st.asas.inconf).copy(),
                     int(st.asas.nconf_cur), int(st.asas.nlos_cur))

    key0, ref = one_interval("DENSE", "MVP")
    print(f"kernels: N={n}, CDMETHOD DENSE (ops/cd.py): "
          f"{int(ref[0].sum())} aircraft in conflict, nconf_cur "
          f"{ref[1]}, nlos_cur {ref[2]} {tag}", flush=True)
    check(ref[1] > 0, "the reference state holds conflicts")
    for backend in ("PALLAS", "SPARSE"):
        for reso in ("MVP", "EBY", "SWARM", "SSD"):
            key, got = one_interval(backend, reso)
            check(key == key0, f"{backend} {reso}: the same seeded state")
            diff = int((got[0] != ref[0]).sum())
            check(diff == 0 and got[1:] == ref[1:],
                  f"{backend} {reso} == DENSE: inconf differs at {diff} "
                  f"of {n}, nconf_cur {got[1]} vs {ref[1]}, nlos_cur "
                  f"{got[2]} vs {ref[2]} {tag}")
        # the program just run, lowered again: Mosaic kernels are
        # tpu_custom_call ops, interpreted ones are plain HLO loops
        hlo = run_steps_edge.lower(sim.traf.state, sim.cfg, 20,
                                   checked=sim.guard.enabled).as_text()
        ncall = hlo.count("tpu_custom_call")
        check(rehearsal or ncall > 0,
              f"{backend}: {ncall} Mosaic kernel call(s) in the lowered "
              f"chunk program {tag}")

    # the two chunk programs that are off by default: compiled and
    # run once, not timed
    knobs = ("SCANSTATS", "FINGERPRINT")
    for on in knobs:
        one_interval("SPARSE", "MVP",
                     *(f"{k} {'ON' if k == on else 'OFF'}" for k in knobs))
    print(f"kernels: backend compiles in this process: "
          f"{counter(sim, 'devprof_backend_compiles')} {tag}", flush=True)
    return dict(device=dev)


def phase_shard(rehearsal):
    """Four chips: N through SHARD REPLICATE 4, SHARD SPATIAL 4 and
    SHARD TILE 2x2, one 1000-step chunk each, against the one-chip run
    of the same seeded fleet (~8 minutes, so ~32 chip-minutes)."""
    import numpy as np
    n = SIZES[rehearsal][0]
    nsteps = 1000
    # spatial/tiles re-bucket callers into per-device shards: 2x slots
    # is the headroom bench.py has always given them
    sim, dev = embedded_sim(2 * n, rehearsal, ndev=4)
    tag = dev_tag(dev)

    def one_chunk(shard):
        run_line(sim, "RESET")
        make_fleet(sim, n, "CDMETHOD SPARSE", "RESO MVP")
        if shard:
            run_line(sim, shard)
            print(f"shard: {sim.scr.echobuf[-1]}", flush=True)
        t0 = time.perf_counter()
        run_line(sim, f"OP; FF {nsteps * SIMDT:g}")
        sim.run(until_simt=nsteps * SIMDT)
        w = time.perf_counter() - t0
        st = sim.traf.state
        check(abs(sim.simt - nsteps * SIMDT) < 1e-2
              and not sim.guard.trips
              and sim.shard_mode == (shard.split()[1].lower()
                                     .replace("tile", "tiles")
                                     if shard else "off"),
              f"{shard or 'one chip'}: {nsteps} steps ran in "
              f"{sim.shard_mode} mode, guard clean ({w:.1f} s wall, "
              f"compile included) {tag}")
        if shard:
            devs = {s.device.id for s in st.ac.lat.addressable_shards}
            check(len(devs) == 4, f"{shard}: the state has a shard on "
                  f"each of devices {sorted(devs)}")
        inconf = np.asarray(st.asas.inconf)
        return ({a: bool(inconf[i]) for i, a in enumerate(sim.traf.ids)
                 if a is not None},
                int(st.asas.nconf_cur), int(st.asas.nlos_cur))

    ref = one_chunk("")
    print(f"shard: one chip: {sum(ref[0].values())} aircraft in "
          f"conflict after {nsteps} steps, nconf_cur {ref[1]}, nlos_cur "
          f"{ref[2]} {tag}", flush=True)
    failed = []
    for shard in ("SHARD REPLICATE 4", "SHARD SPATIAL 4", "SHARD TILE 2x2"):
        try:                 # a mode that fails does not hide the next
            got = one_chunk(shard)
            diff = sum(got[0][a] != v for a, v in ref[0].items())
            what = (f"inconf differs at {diff} of {n}, nconf_cur {got[1]} "
                    f"vs {ref[1]}, nlos_cur {got[2]} vs {ref[2]} {tag}")
            if "TILE" not in shard:
                # the one-chip run's own stripe layout: bit-identical
                check(diff == 0 and got[1:] == ref[1:],
                      f"{shard} == one chip: {what}")
            else:
                # Tiles sort the fleet tile-major, so a resolution sum
                # of three or more terms can round differently from the
                # stripe layout's.  Measured on four v5e chips (PR 21,
                # this fleet): bit-identical positions and conflict sets
                # for 10 CD intervals, one aircraft one ulp apart at the
                # 11th, and the MVP dynamics at this density grow that
                # ~25% per interval: 8% of the flags after 50.  So here
                # the counts must agree closely, not the flags.
                check(all(abs(g - r) <= 0.01 * r
                          for g, r in zip(got[1:], ref[1:])),
                      f"{shard} ~ one chip (counts within 1%): {what}")
        except SmokeFailure as e:
            print(f"  FAILED: {e}", flush=True)
            failed.append(shard)
    if failed:
        raise SmokeFailure(f"{', '.join(failed)} (see above)")
    return dict(device=dev)


PHASE_FN = {"served": phase_served, "warm": phase_warm,
            "kernels": phase_kernels, "shard": phase_shard}


# ===================================================================
def run_child(phase, rehearsal):
    """One phase in this process; the last line is RESULT + json."""
    try:
        out = PHASE_FN[phase](rehearsal)
    except SmokeFailure as e:
        print(f"FAILED {phase}: {e}", flush=True)
        return 1
    print(RESULT + json.dumps(out), flush=True)
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--devices", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearsal", action="store_true",
                    help="toy sizes, CPU allowed: debugs this script")
    ap.add_argument("--phase", choices=sorted(PHASE_FN),
                    help="run one phase in this process (the parent "
                         "starts its children with this)")
    args = ap.parse_args(argv)
    if args.phase:
        return run_child(args.phase, args.rehearsal)

    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    if args.rehearsal and args.devices > 1:
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_host"
                            f"_platform_device_count={args.devices}")
    results = {}
    t_all = time.perf_counter()
    for phase in (PHASES_4 if args.devices == 4 else PHASES_1):
        print(f"==== phase {phase} ====", flush=True)
        t0 = time.perf_counter()
        cmd = [sys.executable, os.path.abspath(__file__), "--phase", phase]
        if args.rehearsal:
            cmd.append("--rehearsal")
        child = subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE,
                                 text=True, start_new_session=True)
        last = ""
        try:
            # (a child that overruns is killed with its whole session
            # by the alarm below)
            signal.signal(signal.SIGALRM, lambda *a: os.killpg(
                child.pid, signal.SIGKILL))
            signal.alarm(PHASE_TIMEOUT[phase])
            for line in child.stdout:
                sys.stdout.write(line)
                sys.stdout.flush()
                last = line
            rc = child.wait()
        finally:
            signal.alarm(0)
            try:
                os.killpg(child.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
        print(f"==== phase {phase}: exit {rc} after "
              f"{time.perf_counter() - t0:.0f} s ====", flush=True)
        if rc != 0 or not last.startswith(RESULT):
            print(f"chip_smoke: phase {phase} failed", flush=True)
            return 1
        results[phase] = json.loads(last[len(RESULT):])

    dev = next(iter(results.values()))["device"]
    if "jax" in sys.modules \
            or any(r["device"] != dev for r in results.values()):
        print(f"chip_smoke: the parent imported jax, or the phases "
              f"disagree on the device: {results}")
        return 1
    if "warm" in results:
        print(f"first 20-step chunk after OP: "
              f"{results['served']['first_chunk_s']} s in the worker, "
              f"{results['warm']['first_chunk_s']} s in a fresh process "
              f"afterwards ({results['warm']['hits']} persistent-cache "
              f"hits) {dev_tag(dev)}")
    print(f"chip_smoke: all phases passed in "
          f"{time.perf_counter() - t_all:.0f} s")
    out = {"ok": True, "device": {"platform": dev["platform"],
                                  "kind": dev["device_kind"],
                                  "count": dev["count"]}}
    if args.rehearsal:
        out = {"ok": True, "rehearsal": True, "device": out["device"]}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
