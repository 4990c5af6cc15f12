"""The networked sim worker: Simulation wrapped in a network Node
(parity: bluesky/simulation/qtgl/simulation.py:204-287 event surface +
network/node.py loop).

Event surface (same tokens as the reference): STACKCMD, STEP, BATCH, QUIT,
GETSIMSTATE.  State changes are reported to the server via STATECHANGE so
the BATCH farm can schedule the next scenario piece on this worker when it
finishes (server.py:234-247 semantics).

OPT BATCH pieces (differentiable workloads, bluesky_tpu/diff/): a piece
whose scenario runs the OPT stack command blocks this loop for the
optimization's duration — the server's busy-PING budget
(hb_busy_multiplier) covers it exactly like a long first compile — then
sends its OPTRESULT upstream on this node's event socket and HOLDs, so
the piece's ``completed`` record follows the journaled ``opt_result``
on the FIFO pair.  The server never packs OPT pieces into world-batches
(the optimizer multi-starts on the world axis internally; see
network/server.py _piece_solo_reason).
"""
from .. import settings
from ..network import node as netnode
from ..network import detached
from ..network.common import IDLE_WAIT_MS
from .sim import Simulation, INIT, HOLD, OP, END
from .screenio import ScreenIO


def _make_simnode_class(base):
    class _SimNode(base):
        def __init__(self, event_port=None, stream_port=None, node_id=None,
                     **simkw):
            # watchdog knobs ride to the Node base, not the Simulation
            nodekw = {k: simkw.pop(k) for k in
                      ("watchdog_warn", "watchdog_kill") if k in simkw}
            super().__init__(
                event_port=event_port or settings.wevent_port,
                stream_port=stream_port or settings.wstream_port,
                node_id=node_id, **nodekw)
            self.sim = Simulation(**simkw)
            self.sim.scr = ScreenIO(self.sim, self)
            self.sim.node = self
            # Packed multi-world BATCH (simulation/worlds.py): the
            # server may dispatch a world-batch of compatible pieces as
            # ONE assignment; while it runs, step() drives the runner
            # instead of the main sim.  Construction kwargs are kept so
            # every world sim shares the worker's nmax bucket.
            self.worlds = None
            self._world_simkw = dict(simkw)
            # broker HA (network/ha.py): the solo BATCH piece currently
            # running, kept so a re-REGISTER after broker failover can
            # report it and the new leader ADOPTS it in place instead
            # of requeueing.  Packs are not reported (their per-world
            # completions already journaled; the rest requeues after
            # the adoption grace).
            self._batch_piece = None
            # Subsystems constructed before the swap hold the headless
            # Screen; repoint them at the streaming ScreenIO
            self.sim.areas.scr = self.sim.scr
            # BATCH stack command: upload the multi-SCEN scenario to
            # the server for farm-out (simulation.py:195-202)
            self.sim.batch = self.batch
            self.prev_state = self.sim.state_flag
            # a BATCH piece's account (docs/OBSERVABILITY.md): the
            # open ``piece`` scope, the open ``node_idle`` scope, and
            # the program-clock stamp of the last STATECHANGE out of OP
            # (the turnaround series)
            self._piece_span = None
            self._idle_span = None
            self._t_piece_done = None

        def batch(self, fname):
            ok, msg = self.sim.stack.openfile(fname)
            if not ok:
                return False, msg
            scentime = self.sim.stack.scentime
            scencmd = self.sim.stack.scencmd
            self.sim.stack.scentime, self.sim.stack.scencmd = [], []
            self.send_event(b"BATCH", {"scentime": scentime,
                                       "scencmd": scencmd})
            return True, "BATCH uploaded to the server"

        def close(self):
            self.sim.scr.close()      # deregister stream timers
            super().close()

        # ------------------------------------------------------ preemption
        def on_preempt_signal(self, signum):
            # SIGTERM from the scheduler: don't die mid-chunk — raise
            # the flag and let step() drain + checkpoint at the edge
            self.sim.request_preempt()

        def _preempt_shutdown(self):
            """Preemption-safe exit: the current chunk has drained
            (sim.step returns at chunk edges), so write the final
            checksummed checkpoint, tell the server (PREEMPTED — the
            in-flight BATCH piece is requeued WITHOUT a circuit-breaker
            strike; STATECHANGE -1 follows from the run() teardown)
            and leave cleanly."""
            sim = self.sim
            path, err = sim.handle_preempt()
            info = {"simt": sim.sent(sim.simt), "ntraf": sim.traf.ntraf}
            if path:
                info["checkpoint"] = path
            if err:
                info["error"] = err
            self.send_event(b"PREEMPTED", info)
            self._batch_piece = None
            sim.stop()
            self.quit()

        # ------------------------------------------------------ multi-world
        def _start_worlds(self, worlds_payload):
            """A packed BATCH assignment: run the worlds through the
            joint-dispatch WorldBatch runner.  Per-world completion is
            reported upstream as ``BATCHWORLD`` events the server
            journals per piece (exactly-once demux); per-world echo
            output streams with a ``[wNN]`` prefix.  The pack is one
            ``piece`` scope, named by its first piece."""
            from .worlds import WorldBatch
            from ..network.journal import BatchJournal
            sim = self.sim
            pieces = [(p["scentime"], p["scencmd"])
                      for p in worlds_payload]
            self._begin_piece(BatchJournal.piece_name(pieces[0]),
                              worlds=len(pieces))
            self._batch_piece = None   # packs are not adoption-reported
            with sim.timed("piece_reset", "sim_piece_reset_ms",
                           cat="node"):
                sim.reset()
            with sim.timed("pack_build", "sim_pack_build_ms",
                           cat="node"):
                self.worlds = WorldBatch(
                    pieces, simkw=self._world_simkw,
                    host_tag=self.node_id.hex()[:8],
                    drained_at=sim._t_drained,
                    on_world_done=lambda w, status, info=None:
                        self.send_event(
                            b"BATCHWORLD",
                            dict({"world": w, "status": status},
                                 **(info or {}))),
                    on_echo=lambda w, text:
                        sim.scr.echo(f"[w{w:02d}] {text}"))
            self.prev_state = OP
            self.send_event(b"STATECHANGE", OP)

        def _finish_worlds(self):
            # the next dispatch, of either kind, counts the device's
            # empty stretch from the pack's last retirement; what the
            # worlds observed since the last heartbeat ships with the
            # worker's own registry
            self.sim._t_drained = self.worlds.drained_at()
            self.sim.obs.merge(self.worlds.obs_delta())
            self.worlds = None
            self.prev_state = HOLD
            self.send_event(b"STATECHANGE", HOLD)
            self._end_piece()

        def _preempt_worlds(self):
            """Preemption mid-pack: checkpoint every active world (one
            tagged file each), tell the server which worlds were
            already done (only the unfinished pieces requeue) and
            leave cleanly."""
            self.sim.preempt_requested = False
            info = self.worlds.handle_preempt()
            self.send_event(b"PREEMPTED", info)
            self.worlds = None
            self.sim.stop()
            self.quit()

        # --------------------------------------------------------- heartbeat
        def register_payload(self):
            """REGISTER payload: the devices this worker runs on (HEALTH
            shows them per worker — the only place the served path says
            which platform it landed on), and the in-flight solo BATCH
            piece, keyed by content (network/journal.py piece_key) —
            what lets the post-failover leader adopt this worker's
            running piece instead of requeueing a second copy
            (server._ha_adopt)."""
            from ..obs.devprof import device_info
            reg = {"device": device_info()}
            if self._batch_piece is not None:
                from ..network.journal import BatchJournal
                sim = self.sim
                reg["inflight"] = {
                    "key": BatchJournal.piece_key(self._batch_piece),
                    "simt": sim.sent(sim.simt_planned),
                    "chunks": int(sim._step_count)}
            return reg

        def heartbeat_payload(self, stamp):
            """Progress piggybacked on the PONG reply: sim-time and
            chunks done let the server's straggler detector tell a
            stalled piece (fresh heartbeats, flat progress) from a
            long device chunk or first compile (no heartbeats at all —
            this loop is blocked, and the busy-PING budget applies)."""
            sim = self.sim
            if self.worlds is not None:
                # packed piece: aggregate progress — the slowest active
                # world's clock advances monotonically while the pack
                # runs, which is exactly the advance signal the
                # straggler detector needs
                info = dict({"stamp": stamp}, **self.worlds.progress())
                obs = self.worlds.obs_delta(also=(sim.obs,))
                if obs:
                    info["obs"] = obs
                # worst-case scan summary across the pack's worlds
                # (peaks max, minima min) — host dicts only, no device
                # reads, same contract as the single-sim branch below
                scans = [s._scan_last for s in self.worlds.sims
                         if s._scan_last is not None]
                if scans:
                    from ..obs import scanstats as _ss
                    info["scan"] = _ss.merge_summaries(scans)
                return info
            # "ff" gates the server's RATE-based hedging: sim-s/wall-s
            # is only comparable across workers running full speed — a
            # wall-clock-paced piece reports ~dtmult by design, which
            # must not read as "far below the fleet median".
            # planned clock: a device read here would block the event
            # loop on the in-flight pipelined chunk, turning "busy" into
            # "silent" for the server's straggler detector
            info = {"stamp": stamp, "simt": sim.sent(sim.simt_planned),
                    "chunks": sim._step_count,
                    "state": sim.state_flag, "ntraf": sim.traf.ntraf,
                    "ff": bool(sim.ffmode)}
            # mesh-epoch health rides the heartbeat so HEALTH can show
            # the fleet's shard state without a round-trip per worker
            if sim.shard_mode != "off" or sim.mesh_epoch > 0:
                info["mesh"] = sim.mesh_health()
            # in-scan telemetry summary (newest drained chunk): a host
            # dict stamped at the chunk edge — reading the device here
            # would block the loop exactly like the planned-clock note
            if sim.cfg.scanstats and sim._scan_last is not None:
                info["scan"] = sim._scan_last
            # SDC fingerprint chain summary: host ints stamped at each
            # drained chunk edge — same no-device-read contract; the
            # server records it per piece for hedge/vote comparison
            fp = sim.fp_summary()
            if fp is not None:
                info["fp"] = fp
            # fleet telemetry: ship the metric increments since the
            # last heartbeat; the server merges them into its fleet
            # registry (METRICS DUMP shows the aggregate)
            obs = sim.obs.delta()
            if obs:
                info["obs"] = obs
            return info

        # ------------------------------------------------------ piece spans
        def event_wait_ms(self):
            """An idle worker waits for its next event at the idle
            loop's pace.  One whose last ``step`` ended with the sim in
            OP, or with a pack running, does not wait: the step to come
            blocks by itself, on the chunk in flight, the pacing sleep
            or a straggle sleep, or dispatches into an empty pipeline
            (a few turns, each a dispatch), so the loop needs no yield
            of its own; a step that leaves OP (a HOLD taken, an FF at
            its horizon, the last world of a pack) opens ``node_idle``
            before it returns, or the step after it does."""
            return IDLE_WAIT_MS if self._idle_span is not None else 0

        def poll(self, timeout_ms):
            sim = self.sim
            if not timeout_ms:
                sim.obs.get("sim_node_turns_nowait").inc()
            with sim.timed("node_poll", "sim_node_poll_ms", cat="node"):
                n = super().poll(timeout_ms)
            idle = self._idle_span
            if idle is not None:
                # how the idle wait ended: an event, or its bound
                cause = "woken" if n else "timed_out"
                sim.obs.get("sim_node_idle_" + cause).inc()
                idle.tag(cause=cause)
            return n

        def _end_idle(self):
            self.sim.timed.end(self._idle_span)
            self._idle_span = None

        def _begin_piece(self, name, **tags):
            """BATCH received: the turnaround since the last piece's
            STATECHANGE is observed and the ``piece`` scope opens, named
            as the journal names the piece (by its SCEN line)."""
            sim = self.sim
            if self._t_piece_done is not None:
                sim.obs.get("sim_piece_turnaround_ms").observe(
                    (sim.devprof.program_time() - self._t_piece_done)
                    * 1e3)
                self._t_piece_done = None
            sim.timed.end(self._piece_span)    # a piece cut short by
            #                                    this one: not booked
            self._piece_span = sim.timed.begin("piece", cat="node",
                                               piece=name, **tags)

        def _end_piece(self):
            """STATECHANGE out of OP is sent: the piece's scope closes,
            its length and own time are observed, and a piece over twice
            the running median (of eight or more) keeps its account:
            counted, one line in the worker's log, a ``piece_slow``
            instant, each naming the parts largest first."""
            sim = self.sim
            sc, self._piece_span = self._piece_span, None
            sim.timed.end(sc)
            ms, own = sc.ms, sc.own_ms
            self._t_piece_done = sc.c0 + ms * 1e-3   # where it closed
            hist = sim.obs.get("sim_piece_ms")
            median = hist.percentile(0.5)
            slow = hist.count >= 8 and ms > 2.0 * median
            hist.observe(ms)
            sim.obs.get("sim_piece_own_ms").observe(own)
            # the span is closed; its tags are the dict its event holds
            sc.tag(own_ms=own, parts=dict(sc.parts))
            if slow:
                name = sc.tags["piece"]
                account = dict(sorted(dict(sc.parts, own=own).items(),
                                      key=lambda kv: -kv[1]))
                sim.obs.get("sim_piece_slow").inc()
                print(f"node {self.node_id.hex()[:8]}: slow piece "
                      f"{name}: {ms:.1f} ms (median "
                      f"{median:.1f}): "
                      + ", ".join(f"{k} {v:.1f}"
                                  for k, v in account.items()),
                      flush=True)
                sim.recorder.instant("piece_slow", cat="node",
                                     piece=name, ms=ms, parts=account)

        def _start_piece(self, data):
            """A solo BATCH piece: the ``piece`` scope opens, then the
            reset, the scenario and OP."""
            sim = self.sim
            from ..network.journal import BatchJournal
            self._batch_piece = (data["scentime"], data["scencmd"])
            self._begin_piece(BatchJournal.piece_name(self._batch_piece))
            with sim.timed("piece_reset", "sim_piece_reset_ms",
                           cat="node"):
                sim.reset()
            sim.stack.set_scendata(data["scentime"], data["scencmd"])
            sim.op()

        # ------------------------------------------------------------ events
        def event(self, name, data, sender_route):
            sim = self.sim
            self._end_idle()
            if name == b"STACKCMD":
                cmd = data["cmd"] if isinstance(data, dict) else str(data)
                # Reply route = REVERSED accumulated sender tail (see
                # network/server.py routing note); comma-joined hex so
                # the stack's plain-string sender survives multi-hop.
                sender = ",".join(f.hex() for f in reversed(sender_route)) \
                    if sender_route else ""
                sim.stack.stack(cmd, sender)
            elif name == b"STEP":
                # lockstep: advance exactly dtmult seconds of sim time
                # (possibly several quantized chunks), then ack
                sim.op()
                t_target = sim.simt_planned + sim.dtmult
                while sim.state_flag == OP \
                        and sim.simt_planned < t_target - 1e-9:
                    nsteps = max(1, int(round(
                        (t_target - sim.simt_planned) / sim.simdt)))
                    sim.step(max_chunk=nsteps)
                sim.pause()
                self.send_event(b"STEP", None,
                                list(reversed(sender_route)) or None)
            elif name == b"BATCH":
                if isinstance(data, dict) and data.get("worlds"):
                    self._start_worlds(data["worlds"])
                else:
                    self._start_piece(data)
            elif name == b"BATCHCANCEL":
                # the server hedged this piece and the other copy won:
                # ack FIRST (the FIFO event pair is how the server
                # tells a cancel ack from a duplicate completion), then
                # abandon the piece — the reset's STATECHANGE makes
                # this worker available again
                self.send_event(b"BATCHCANCELLED", None)
                self._batch_piece = None
                if self.worlds is not None:
                    self.worlds = None
                    self.prev_state = sim.state_flag
                    self.send_event(b"STATECHANGE", HOLD)
                sim.reset()
            elif name == b"BATCHREJECTED":
                d = data or {}
                sim.scr.echo(
                    f"BATCH rejected by the server: queue "
                    f"{d.get('queue_depth', '?')}/{d.get('limit', '?')} "
                    f"full — retry in {d.get('retry_after', '?')} s")
            elif name == b"HEALTH":
                # reply to the stack HEALTH command's server query
                txt = data.get("text") if isinstance(data, dict) \
                    else str(data)
                sim.scr.echo(txt or "no health data")
            elif name == b"WORLDS":
                # reply to the stack WORLDS command's server query/set
                txt = data.get("text") if isinstance(data, dict) \
                    else str(data)
                sim.scr.echo(txt or "no worlds data")
            elif name == b"MITIGATE":
                # reply to the stack MITIGATE command's server query/set
                txt = data.get("text") if isinstance(data, dict) \
                    else str(data)
                sim.scr.echo(txt or "no mitigation data")
            elif name == b"SDC":
                # reply to the stack SDC command's server query/set
                txt = data.get("text") if isinstance(data, dict) \
                    else str(data)
                sim.scr.echo(txt or "no sdc data")
            elif name == b"HA":
                # reply to the stack HA STATUS command's server query
                txt = data.get("text") if isinstance(data, dict) \
                    else str(data)
                sim.scr.echo(txt or "no ha data")
            elif name == b"METRICS":
                # reply to METRICS DUMP's server query: broker + fleet
                # registries rendered server-side
                txt = data.get("text") if isinstance(data, dict) \
                    else str(data)
                sim.scr.echo(txt or "no metrics data")
            elif name == b"TRACE":
                # reply to TRACE DUMP's server-side ring dump
                d = data if isinstance(data, dict) else {}
                sim.scr.echo(
                    f"server trace: {d.get('path') or 'ring empty'}"
                    if d.get("enabled")
                    else "server trace: recorder disabled")
            elif name == b"GETSIMSTATE":
                self.send_event(b"SIMSTATE", {
                    "state": sim.state_flag,
                    "simt": sim.sent(sim.simt_planned),
                    "simdt": sim.simdt, "ntraf": sim.traf.ntraf},
                    list(reversed(sender_route)) or None)
            elif name == b"QUIT":
                sim.stop()
                self.quit()

        # -------------------------------------------------------------- step
        def step(self):
            sim = self.sim
            self._end_idle()
            sim.scr.update()
            if self.worlds is not None:
                running = self.worlds.step()
                if sim.preempt_requested and self.running:
                    self._preempt_worlds()
                    return
                if not running:
                    self._finish_worlds()
                return
            alive = sim.step()
            # mesh-epoch transitions (device-group loss + recovery)
            # queued by sim._handle_mesh_lost — tell the server so it
            # journals the mesh_lost/resharded audit pair (or requeues
            # the piece PREEMPTED-style when recovery failed)
            while sim.mesh_events:
                self.send_event(b"MESHLOST", sim.mesh_events.pop(0))
            if sim.preempt_requested and self.running:
                self._preempt_shutdown()
                return
            if sim.state_flag != self.prev_state:
                was_op = self.prev_state == OP
                piece_done = was_op and self._batch_piece is not None
                self.prev_state = sim.state_flag
                if was_op and sim.state_flag != OP:
                    self._batch_piece = None   # piece left flight
                    # completion fingerprint: SDCFP rides the FIFO
                    # event pair ahead of the STATECHANGE, so the
                    # server can journal/compare it against the piece
                    # this worker still has in flight (the OPTRESULT
                    # ordering contract)
                    fp = sim.fp_summary()
                    if fp is not None:
                        self.send_event(b"SDCFP", fp)
                self.send_event(b"STATECHANGE", sim.state_flag)
                if piece_done:
                    # the piece ends where the broker hears of it: in
                    # the turn in which the state changed
                    self._end_piece()
            if sim.state_flag != OP:
                # node_idle: from here over the loop's wait for the
                # next event (event_wait_ms, poll), until that event
                # or the next step (_end_idle)
                self._idle_span = sim.timed.begin(
                    "node_idle", "sim_node_idle_ms", cat="node")
            if not alive or sim.state_flag == END:
                self.quit()

    return _SimNode


SimNode = _make_simnode_class(netnode.Node)
DetachedSimNode = _make_simnode_class(detached.Node)
