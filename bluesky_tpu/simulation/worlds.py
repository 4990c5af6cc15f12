"""Multi-world execution: W independent scenario worlds per worker,
stepped as ONE stacked device program per chunk.

The serving problem this solves (docs/PERF_ANALYSIS.md §multi-world):
a fleet of small-N BATCH pieces leaves the chip almost idle when every
piece occupies a whole worker process — N=500 uses a fraction of a
percent of the arithmetic an accelerator offers per step.  The server
therefore packs compatible pieces into a *world-batch*
(network/server.py) and ships them to one worker, which runs them
through this module: W full ``Simulation`` instances own their world's
host state (stack, routes, conditionals, loggers — each with its own
tagged ``LogRegistry`` so file output demuxes per world), while the
device-side stepping is batched: each iteration plans every world's
next chunk (``Simulation._plan_chunk``), groups worlds whose compiled
program is identical (same ``SimConfig``, same guard setting, same
nmax by construction), stacks their state pytrees along a leading
world axis and dispatches ``core.step.run_steps_worlds_edge`` ONCE for
the whole group.  Per-world scalars (simt, guard word, telemetry pack)
come back as [W]-vectors and are sliced back to each world's
``_apply_chunk_result`` — guard response, conditionals, trails,
loggers and snapshot captures all stay per-world.

Correctness-first grouping: a world whose configuration cannot join a
stacked dispatch (multi-chip shard mode — spatial stripes are a
per-world layout property and compose with the world axis later, not
now) steps UNBATCHED through its own synchronous chunk path, with a
structured echo instead of a crash.  Worlds at different sim times
batch fine (each carries its own clock); worlds whose chunk plans
differ step the group at the smallest planned chunk (triggers are
stop-at-or-before bounds, and ladder minima are ladder values, so no
compile storm).

Completion mirrors single-piece serving semantics: a world is complete
when its sim leaves OP (scenario HOLD/END); the ``on_world_done``
callback reports it upstream — the node turns that into a per-world
``BATCHWORLD`` event the server journals for exactly-once demux.  A
guard trip under policy ``halt`` marks the world FAILED (the server
strikes/requeues that piece alone); ``quarantine``/``rollback`` worlds
recover per-world and complete normally.
"""
import time
from typing import Callable, List, Optional, Tuple

from .sim import Simulation, HOLD, OP, END


class WorldBatch:
    """W scenario worlds advancing through joint stacked dispatches."""

    def __init__(self, pieces: List[Tuple[list, list]], simkw=None,
                 on_world_done: Optional[Callable] = None,
                 on_echo: Optional[Callable] = None,
                 host_tag: str = "", drained_at: Optional[float] = None):
        from ..utils.datalog import LogRegistry
        simkw = dict(simkw or {})
        self.on_world_done = on_world_done
        self.on_echo = on_echo
        self.status: List[Optional[str]] = [None] * len(pieces)
        self.t0 = time.monotonic()
        self.stats = {"joint_dispatches": 0, "solo_dispatches": 0,
                      "worlds_stepped": 0, "max_group": 0,
                      "solo_sharded": 0}
        self._solo_echoed = set()
        self.sims: List[Simulation] = []
        for i, (scentime, scencmd) in enumerate(pieces):
            tag = f"w{i:02d}"
            sim = Simulation(datalog_registry=LogRegistry(tag=tag),
                             world_tag=tag, **simkw)
            # world sims have no .node: the owning worker's id keeps
            # preempt checkpoints unique across workers sharing a dir
            sim.host_tag = str(host_tag)
            # joint dispatch is synchronous by construction: every edge
            # retires before the next stacked chunk is planned
            sim.pipeline_enabled = False
            # the stretch the device has held no chunk began before the
            # pack did: the owner's stamp, for the first dispatch to
            # close (``Simulation._note_pipeline_empty``)
            sim._t_drained = drained_at
            sim.stack.set_scendata(list(scentime), list(scencmd))
            sim.op()
            self.sims.append(sim)

    # ------------------------------------------------------------- status
    @property
    def nworlds(self) -> int:
        return len(self.sims)

    @property
    def active(self) -> List[int]:
        return [i for i, s in enumerate(self.status) if s is None]

    @property
    def done(self) -> bool:
        return not self.active

    def progress(self) -> dict:
        """Aggregate progress for the worker heartbeat: the straggler
        detector needs ADVANCE, so report the slowest active world's
        clock and the summed chunk count."""
        act = [self.sims[i] for i in self.active]
        return {
            "simt": min((s.sent(s.simt_planned) for s in act),
                        default=0.0),
            "chunks": sum(s._step_count for s in self.sims),
            "state": OP if act else HOLD,
            "ntraf": sum(s.traf.ntraf for s in self.sims),
            "ff": any(s.ffmode for s in act),
            "worlds": self.nworlds,
            "worlds_done": self.nworlds - len(act),
        }

    def obs_delta(self, also=()) -> dict:
        """Summed metric increments of every world sim since the last
        call — the pack's contribution to the worker heartbeat's fleet
        telemetry (counters/histograms add exactly; gauges last-world)
        — and of the registries in ``also``: the worker's own, which
        books the pack's ``piece``, its reset and its build.
        """
        from ..obs.metrics import Registry
        agg = Registry()
        for reg in (*also, *(sim.obs for sim in self.sims)):
            agg.merge(reg.delta())
        return agg.delta()

    def drained_at(self) -> Optional[float]:
        """When the wait that retired the pack's newest chunk returned
        (program clock; every edge of a pack retires before the next
        dispatch), for the owner's next dispatch to count from."""
        return max((s._t_drained for s in self.sims
                    if s._t_drained is not None), default=None)

    # -------------------------------------------------------------- step
    def step(self) -> bool:
        """One joint host iteration: plan every active world, dispatch
        compatible plans as stacked world-batches, apply per-world
        edges.  Returns False once every world completed."""
        plans = []
        for i in self.active:
            sim = self.sims[i]
            if sim.state_flag == END:
                self._finish(i)
                continue
            plan = sim._plan_chunk(None)
            self._drain_echo(i)
            if plan is None:
                # no device chunk this iteration; leaving OP completes
                # the piece (single-worker STATECHANGE semantics)
                if sim.state_flag != OP:
                    self._finish(i)
                continue
            plans.append((i, sim) + plan)

        groups = {}
        solo = []
        for i, sim, chunk, simt in plans:
            if sim.shard_mode != "off" or sim.cfg.cd_mesh is not None:
                # the world-axis batch composes with single-device
                # configs only — sharded worlds step unbatched, loudly
                if i not in self._solo_echoed:
                    self._solo_echoed.add(i)
                    self.stats["solo_sharded"] += 1
                    self._echo(i, f"WORLDS: world {i} runs shard_mode="
                                  f"{sim.shard_mode} — stepping "
                               "unbatched (world-batching composes "
                               "with sharding later, not now)")
                solo.append((i, sim, chunk, simt))
            else:
                groups.setdefault((sim.cfg, sim.guard.enabled),
                                  []).append((i, sim, chunk, simt))

        from ..core.step import (run_steps_worlds_edge, stack_worlds,
                                 world_slice)
        for (cfg, checked), members in groups.items():
            if len(members) == 1:
                solo.append(members[0])
                continue
            chunk = min(m[2] for m in members)
            states = [sim._pre_dispatch_refresh(sim.traf.state, simt,
                                                chunk)
                      for i, sim, c, simt in members]
            # one dispatch, W worlds: each member still gets its OWN
            # seq correlation tag, so the per-world chunk_edge spans
            # demux cleanly on the merged timeline
            seqs = [sim._next_seq() for i, sim, c, simt in members]
            # one program for the pack, so one dense row count: the
            # largest of its worlds' (same config, same nmax), and one
            # observation of the dispatch's series, in the first
            # world's registry
            lead = members[0][1]
            cfg, rows = lead.chunk_cfg(
                pack=[sim for i, sim, c, simt in members[1:]])
            with lead.timed("chunk_dispatch", "sim_dispatch_ms",
                            cat="worlds", chunk=chunk,
                            nworlds=len(members),
                            worlds=[i for i, s, c, t in members],
                            seqs=seqs) as sp:
                lead._note_cd_rows(rows, sp)
                lead._note_pipeline_empty(lead.devprof.program_time())
                out = run_steps_worlds_edge(
                    stack_worlds(states), cfg, chunk, checked=checked)
            self.stats["joint_dispatches"] += 1
            self.stats["worlds_stepped"] += len(members)
            self.stats["max_group"] = max(self.stats["max_group"],
                                          len(members))
            for k, (i, sim, c, simt) in enumerate(members):
                if c > chunk and sim.syst >= 0:
                    # _plan_chunk charged the wall-clock pacing anchor
                    # for the FULL planned chunk; the group executed
                    # the group-min — rebate the difference so a packed
                    # non-FF world doesn't accrue a pacing deficit
                    sim.syst -= (c - chunk) * sim.cfg.simdt \
                        / max(sim.dtmult, 1e-9)
                sim.pipe_stats["sync_chunks"] += 1
                # every output leads with [W] and demuxes per world
                # alike (a pack that is off stays None)
                state, telem, sstats, fpack = world_slice(out, k)
                sim._apply_chunk_result(state, telem, chunk,
                                        seq=seqs[k], stats=sstats,
                                        fingerprint=fpack)
                sim._after_chunk()
                self._drain_echo(i)
                self._maybe_finish(i)

        for i, sim, chunk, simt in solo:
            self.stats["solo_dispatches"] += 1
            self.stats["worlds_stepped"] += 1
            sim._step_sync(chunk, sim.simt)
            sim._after_chunk()
            self._drain_echo(i)
            self._maybe_finish(i)

        return not self.done

    def run(self, max_iters: int = 10 ** 9) -> List[Optional[str]]:
        """Drive step() until every world completed; returns statuses."""
        it = 0
        while it < max_iters and self.step():
            it += 1
        return list(self.status)

    # -------------------------------------------------------- completion
    def _maybe_finish(self, i: int):
        if self.status[i] is None and self.sims[i].state_flag != OP:
            self._finish(i)

    def _finish(self, i: int):
        sim = self.sims[i]
        # a guard trip under policy 'halt' froze the corrupt world —
        # report it failed so the server strikes/requeues THAT piece
        # alone; quarantine/rollback worlds recovered per-world and
        # completed like any clean run
        failed = sim.guard.policy == "halt" and bool(sim.guard.trips)
        self.status[i] = "failed" if failed else "completed"
        if self.on_world_done is not None:
            info = {"simt": sim.sent(sim.simt_planned),
                    "ntraf": sim.traf.ntraf,
                    "trips": len(sim.guard.trips)}
            fp = sim.fp_summary()
            if fp is not None:
                info["fp"] = fp
            self.on_world_done(i, self.status[i], info)

    # ------------------------------------------------------ preempt/echo
    def handle_preempt(self) -> dict:
        """Preemption mid-pack: checkpoint every ACTIVE world to its own
        tagged file (sim.handle_preempt uses world_tag) and report what
        was already done — the server requeues only unfinished pieces."""
        info = {"worlds": self.nworlds,
                "done": [i for i, s in enumerate(self.status)
                         if s == "completed"],
                "checkpoints": []}
        for i in self.active:
            path, err = self.sims[i].handle_preempt()
            if path:
                info["checkpoints"].append(path)
            if err:
                info.setdefault("errors", []).append(err)
        return info

    def _echo(self, i: int, text: str):
        if self.on_echo is not None:
            self.on_echo(i, text)

    def _drain_echo(self, i: int):
        buf = getattr(self.sims[i].scr, "echobuf", None)
        if buf:
            lines, buf[:] = list(buf), []
            for line in lines:
                self._echo(i, line)
