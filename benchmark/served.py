"""The served path, as the harness drives it: the broker started as
``python -m bluesky_tpu --headless``, the workers it spawns (which alone
import JAX and hold the chips: one, unless the configuration's
``deployment.workers`` says more) and a ``network.client.Client`` in this
process, which never imports JAX.

``Session``, ``command_error``, ``metric`` and the device rule are copies
of ``chip_smoke.py``'s (PR 21), kept here so that a later change to that
script cannot move the yardstick.  Every stamp is ``time.perf_counter()``
on the client's side of the broker.
"""
import glob
import json
import os
import signal
import socket
import subprocess
import sys
import time

import zmq


class HarnessFailure(Exception):
    """The run cannot produce a result (no chip, a dead broker, a
    compile inside the window, a refused command)."""


def command_error(echo):
    """The stack's three ways to say a command did not run."""
    return (" failed: " in echo or echo.startswith("Unknown command")
            or echo.startswith("Usage:"))


def metric(dump, name):
    """One series of a METRICS DUMP echo (Registry.text format); of a
    fleet's dumps (``{worker id: text}``) the sum over the workers that
    have it: a counter of the fleet (a gauge read so is the fleet's
    summed level, README "Workers")."""
    if isinstance(dump, dict):
        found = [v for v in (metric(t, name) for t in dump.values())
                 if v is not None]
        return sum(found) if found else None
    for ln in dump.splitlines():
        if ln.startswith(name + ":"):
            return float(ln.split(":", 1)[1].split()[0])
    return None


def fleet_device(per_worker):
    """``Served.device`` of the workers HEALTH lists (``[{"worker": hex
    id, "platform", "device_kind", "count"}]``, by id): the first's
    platform and kind, the **sum** of their counts."""
    first = per_worker[0]
    return {"platform": first["platform"],
            "device_kind": first["device_kind"],
            "count": sum(int(w["count"]) for w in per_worker),
            "workers": len(per_worker), "per_worker": per_worker}


def require_device(dev, chips, rehearsal):
    """The rules on the devices the fleet found.  ``chips`` None: a
    fleet that is still growing, held to the platform and rehearsal
    rules alone (``Served.expect_workers`` holds the count)."""
    def one(w):
        return f"platform {w['platform']!r}, device_kind " \
               f"{w['device_kind']!r}, {w['count']} device(s)"
    tag = one(dev)
    if dev["workers"] > 1:
        tag += f" over {dev['workers']} workers (" + "; ".join(
            f"worker {w['worker']}: {one(w)}"
            for w in dev["per_worker"]) + ")"
    if dev["platform"] == "cpu" and not rehearsal:
        raise HarnessFailure(
            f"JAX found no accelerator: {tag}.  The benchmark measures "
            "the chip; --rehearsal runs toy sizes on a named CPU")
    if dev["platform"] != "cpu" and rehearsal:
        raise HarnessFailure(f"--rehearsal is for the CPU, found {tag}")
    if len({(w["platform"], w["device_kind"])
            for w in dev["per_worker"]}) > 1:
        raise HarnessFailure(f"the workers found different devices: {tag}")
    if chips is not None and dev["count"] < chips:
        raise HarnessFailure(f"the cell asks for {chips} chip(s): {tag}")


def free_ports(n):
    socks = [socket.socket() for _ in range(n)]
    try:
        for s in socks:
            s.bind(("127.0.0.1", 0))
        return [s.getsockname()[1] for s in socks]
    finally:
        for s in socks:
            s.close()


class Session:
    """A network Client plus what it has seen, each with its stamp."""

    def __init__(self, client):
        self.client = client
        self.echo = []             # (stamp, text, sender)
        self.siminfo = []          # (stamp, simt)
        self.acdata_t = []         # arrival stamps of ACDATA frames
        self.acdata = None         # newest ACDATA frame
        self.keep_frames = None    # list to keep whole frames in, or None
        self.simstate = None
        client.event_received.connect(self._on_event)
        client.stream_received.connect(self._on_stream)
        self._poller = zmq.Poller()
        self._poller.register(client.event_io, zmq.POLLIN)
        self._poller.register(client.stream_in, zmq.POLLIN)

    def _on_event(self, name, data, sender):
        if name == b"ECHO":
            self.echo.append((time.perf_counter(),
                              str((data or {}).get("text", "")), sender))
        elif name == b"SIMSTATE":
            self.simstate = data

    def _on_stream(self, name, data, sender):
        now = time.perf_counter()
        if name == b"ACDATA":
            self.acdata_t.append(now)
            self.acdata = data
            # keep one frame per simulated time: a held world repeats
            # its frame five times a second, 10 MB each at N=100k
            if self.keep_frames is not None and (
                    not self.keep_frames or data["simt"]
                    > self.keep_frames[-1]["simt"] + 1e-3):
                self.keep_frames.append(data)
        elif name == b"SIMINFO":
            self.siminfo.append((now, float(data["simt"])))

    def poll(self, timeout_ms):
        """Wait for either socket, then drain both: a stream frame is
        stamped when it arrives, not when the event socket times out."""
        self._poller.poll(timeout_ms)
        self.client.receive(0)

    def pump(self, seconds):
        t_end = time.perf_counter() + seconds
        while time.perf_counter() < t_end:
            self.poll(10)

    def wait(self, pred, timeout, what, each=None):
        t_end = time.perf_counter() + timeout
        while True:
            self.poll(10)
            if each is not None:
                each()
            if pred():
                return time.perf_counter()
            if time.perf_counter() > t_end:
                raise HarnessFailure(f"timed out waiting for {what}")

    def wait_state(self, pred, timeout, what):
        """Ask GETSIMSTATE until pred(reply); one request outstanding."""
        t_end = time.perf_counter() + timeout
        while True:
            self.simstate = None
            self.client.send_event(b"GETSIMSTATE")
            t = self.wait(lambda: self.simstate is not None,
                          max(0.1, t_end - time.perf_counter()), what)
            if pred(self.simstate):
                return t, self.simstate
            self.pump(0.05)

    def command(self, line, expect, timeout=120.0, target=None):
        """Send one stack line (to the worker ``target``, or the active
        one), return the first new echo containing ``expect`` (from
        that worker)."""
        n0 = len(self.echo)
        self.client.stack(line, target)

        def got():
            return [e for _, e, w in self.echo[n0:]
                    if expect in e and target in (None, w)]
        self.wait(got, timeout, f"the echo of {line!r}")
        return got()[0]

    def failed_commands(self):
        return [e for _, e, _ in self.echo if command_error(e)]


class Served:
    """Broker + workers + client for one run; ``close`` ends them all.
    ``workers``: the fleet the configuration states; ``chips``: what
    the cell asks of it (``expect_workers`` holds the fleet to both)."""

    def __init__(self, repo, rundir, settings, rehearsal, workers=1,
                 chips=None):
        import bluesky_tpu  # noqa: F401 — names the compile cache, which
        #                     the broker and the worker inherit
        from bluesky_tpu.network.client import Client
        if "jax" in sys.modules:
            raise HarnessFailure("the harness process imported jax: it "
                                 "would hold the chip the worker needs")
        self.rundir, self.rehearsal = rundir, rehearsal
        self.workers, self.chips = int(workers), chips
        self.outdir = os.path.join(rundir, "output")
        os.makedirs(self.outdir, exist_ok=True)
        cfgfile = os.path.join(rundir, "settings.cfg")
        lines = [f"{k} = {v!r}" for k, v in settings.items()]
        lines += ["telnet_port = 0", f"log_path = {self.outdir!r}"]
        with open(cfgfile, "w") as f:
            f.write("\n".join(lines) + "\n")
        env = dict(os.environ)
        env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
        # the compile cache at a fixed path inside the checkout, whatever
        # the machine's environment names: two checkouts share nothing
        env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(repo, ".jax_cache")
        if rehearsal:
            env["JAX_PLATFORMS"] = "cpu"
        ev, st = free_ports(2)
        self.log = open(os.path.join(rundir, "broker.log"), "w")
        # its own session: the worker is its child, and killpg reaches
        # both whatever state they are in
        self.broker = subprocess.Popen(
            [sys.executable, "-m", "bluesky_tpu", "--headless",
             "--config-file", cfgfile, "--event-port", str(ev),
             "--stream-port", str(st)],
            cwd=repo, env=env, stdout=self.log, stderr=subprocess.STDOUT,
            start_new_session=True)
        self.client = Client()
        try:
            self.client.connect(event_port=ev, stream_port=st,
                                timeout=60.0)
            self.s = Session(self.client)
            self.s.wait(lambda: bool(self.client.nodes), 240.0,
                        "the worker to register with the broker")
            found = self.fleet()
            if not found:
                raise HarnessFailure("HEALTH carries no device")
            # a fleet grows after the first registration: the first
            # worker alone until ``expect_workers``
            self.device = fleet_device(found[:1])
        except BaseException:
            self.close()
            raise

    def health(self):
        self.client.last_health = None
        self.client.request_health()
        self.s.wait(lambda: self.client.last_health is not None, 60.0,
                    "HEALTH")
        return self.client.last_health

    def fleet(self):
        """The workers HEALTH lists with a device, by id: ``[{"worker":
        hex id, "platform", "device_kind", "count"}]``."""
        return [dict(w["device"], worker=wid)
                for wid, w in sorted(self.health()["workers"].items())
                if isinstance(w.get("device"), dict)]

    def worker_ids(self):
        """The fleet's workers as the client addresses them, in the
        order of ``device["per_worker"]``."""
        return [bytes.fromhex(w["worker"])
                for w in self.device["per_worker"]]

    def expect_workers(self, timeout=600.0):
        """Wait until HEALTH lists the fleet the configuration states,
        each worker with its device (a window calls this once the
        backlog that makes the broker spawn them is in; HEALTH is asked
        twice a second), then hold the fleet to the cell's chips by the
        sum of its counts."""
        t_end = time.perf_counter() + timeout
        found = self.fleet()
        while len(found) < self.workers and time.perf_counter() < t_end:
            self.s.pump(0.5)
            found = self.fleet()
        print("run: " + "; ".join(
            f"worker {w['worker']} on platform {w['platform']}, device_kind "
            f"{w['device_kind']}, count {w['count']}" for w in found),
            file=sys.stderr, flush=True)
        if len(found) != self.workers:
            raise HarnessFailure(
                f"the configuration states {self.workers} worker(s) and "
                f"HEALTH lists {len(found)} with a device after "
                f"{timeout:g} s")
        self.device = fleet_device(found)
        require_device(self.device, self.chips, self.rehearsal)

    def fleet_metrics(self):
        """The broker's METRICS payload: its own registry and the fleet
        aggregate of worker heartbeats (histograms with sum and count)."""
        self.client.last_metrics = None
        self.client.request_metrics()
        self.s.wait(lambda: self.client.last_metrics is not None, 60.0,
                    "METRICS")
        return self.client.last_metrics

    def worker_metrics(self):
        """Each worker's own registry, as text, at this instant:
        ``{worker id: METRICS DUMP echo}``, asked of one after the
        other in the order of ``device["per_worker"]``."""
        return {wid: self.s.command("METRICS DUMP", "sim registry:",
                                    target=wid)
                for wid in self.worker_ids()}

    def journal_lines(self, state):
        """New records of the broker's BATCH journal since the last
        call, each with the stamp at which this client saw it."""
        out = []
        if state.get("path") is None:
            found = glob.glob(os.path.join(self.outdir, "batch-*.jsonl"))
            if not found:
                return out
            state["path"], state["pos"], state["buf"] = found[0], 0, ""
        with open(state["path"]) as f:
            f.seek(state["pos"])
            chunk = f.read()
            state["pos"] = f.tell()
        now = time.perf_counter()
        state["buf"] += chunk
        *done, state["buf"] = state["buf"].split("\n")
        for ln in done:
            if ln.strip():
                out.append((now, json.loads(ln)))
        return out

    def log_tail(self, n=4000):
        self.log.flush()
        with open(self.log.name) as f:
            return f.read()[-n:]

    def close(self):
        try:
            self.client.close()
        except Exception:      # noqa: BLE001 — closing on the way out
            pass
        if self.broker.poll() is None:
            self.broker.terminate()
            try:
                self.broker.wait(timeout=10)
            except subprocess.TimeoutExpired:
                pass
        try:
            os.killpg(self.broker.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
        self.broker.wait()
        # the worker is the broker's child in the same group: wait until
        # the group is empty, so the chip is free for whoever comes next
        t_end = time.time() + 20
        while time.time() < t_end:
            try:
                os.killpg(self.broker.pid, 0)
            except (ProcessLookupError, PermissionError):
                break
            time.sleep(0.1)
        self.log.close()
