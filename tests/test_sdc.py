"""Silent-data-corruption defense (ISSUE-17; docs/FAULT_TOLERANCE.md
§SDC defense).

* Fingerprint fold (obs/fingerprint.py): deterministic, chunking-
  invariant, sensitive to a single flipped mantissa bit the isfinite
  guard cannot see, field-transposition-sensitive; the OFF path steps a
  bit-identical state.
* Sim integration: the FINGERPRINT command toggles the jit-static flag,
  ``fp_summary`` ships the chained witness, FAULT BITFLIP corrupts the
  payload word or the live state.
* Server defense: SDCFP recording keyed by piece CONTENT, hedge-dup /
  shadow-audit comparison -> audit-only ``sdc_suspect`` + a 2-of-3
  vote re-execution, the out-voted worker quarantined through the
  mitigation engine's gated, journaled ``mitigation`` record; vote and
  audit copies are journaled ``queued {synthetic}`` and NEVER
  ``completed``, so replay stays exactly-once.
* Closed-loop chaos acceptance (slow): a live 3-worker fabric with SDC
  ON, hedging ON and mitigation ON absorbs a FAULT BITFLIP on one
  worker — detected by fingerprint mismatch, voted 2-of-3, the deviant
  quarantined — with ZERO operator commands, proven from the journal.
"""
import pickle
import time

import numpy as np
import pytest

zmq = pytest.importorskip("zmq")

import jax
import jax.numpy as jnp

from bluesky_tpu.core.step import GUARD_FIELDS, SimConfig, run_steps_edge
from bluesky_tpu.core.traffic import Traffic
from bluesky_tpu.network.common import make_id
from bluesky_tpu.network.journal import BatchJournal
from bluesky_tpu.network.npcodec import packb
from bluesky_tpu.network.server import Server
from bluesky_tpu.obs import fingerprint as fpmod
from tests.test_mitigate import _bare, _close
from tests.test_network import free_ports, wait_for
from tests.test_overload import _records


# ----------------------------------------------------------------- helpers
def _piece(i, tag="SD"):
    return ([0.0], [f"SCEN {tag}{i}"])


def _copy(tree):
    return jax.tree_util.tree_map(jnp.copy, tree)


def _make_state(n=6, nmax=8, seed=0):
    rng = np.random.default_rng(seed)
    traf = Traffic(nmax=nmax, dtype=jnp.float32)
    traf.create(n, "B744",
                rng.uniform(9000.0, 9300.0, n),
                rng.uniform(140.0, 200.0, n), None,
                52.0 + rng.uniform(-0.2, 0.2, n),
                4.0 + rng.uniform(-0.2, 0.2, n),
                rng.uniform(0.0, 360.0, n))
    traf.flush()
    return traf.state


def _flip_bit(arr, idx=0, bit=2):
    """Flip one mantissa bit of element ``idx`` — finite in, finite
    out, so the isfinite guard is blind to it by construction."""
    a = np.asarray(arr)
    word = {4: np.uint32, 8: np.uint64}[a.dtype.itemsize]
    raw = a.view(word).copy()
    raw[idx] ^= word(1 << bit)
    return jnp.asarray(raw.view(a.dtype))


def _sdc_records(jpath):
    recs = _records(jpath)
    return ([r for r in recs if r["rec"] == "sdc_suspect"],
            [r for r in recs if r["rec"] == "sdc_vote"],
            [r for r in recs if r["rec"] == "mitigation"])


# ------------------------------------------------------ fingerprint fold
class TestFingerprintFold:
    def test_deterministic_and_state_sensitive(self):
        cfg = SimConfig()
        state = _make_state()
        pack = fpmod.fold(fpmod.init(state, cfg), state, cfg)
        again = fpmod.fold(fpmod.init(state, cfg), state, cfg)
        assert fpmod.combine(pack) == fpmod.combine(again)
        assert fpmod.combine(pack) != 0
        # one flipped mantissa bit in one lat element changes the word
        # while the guard's finite check stays clean
        flipped = state.replace(
            ac=state.ac.replace(lat=_flip_bit(state.ac.lat)))
        assert bool(np.isfinite(np.asarray(flipped.ac.lat)).all())
        corrupt = fpmod.fold(fpmod.init(flipped, cfg), flipped, cfg)
        assert fpmod.combine(corrupt) != fpmod.combine(pack)

    def test_field_transposition_detected(self):
        """XOR alone would miss two watched columns swapping values;
        the per-field rotation must not."""
        cfg = SimConfig()
        state = _make_state()
        assert "lat" in GUARD_FIELDS and "lon" in GUARD_FIELDS
        swapped = state.replace(ac=state.ac.replace(
            lat=state.ac.lon.astype(state.ac.lat.dtype),
            lon=state.ac.lat.astype(state.ac.lon.dtype)))
        a = fpmod.combine(fpmod.fold(fpmod.init(state, cfg), state, cfg))
        b = fpmod.combine(fpmod.fold(fpmod.init(swapped, cfg),
                                     swapped, cfg))
        assert a != b

    def test_chunk_scan_off_parity_and_chunking_invariance(self):
        """The ON chunk scan steps a bit-identical state to OFF, and
        the host ``chain`` recurrence makes the witness invariant to
        re-chunking: one 8-step chunk == eight chained 1-step chunks."""
        state = _make_state()
        off_state = run_steps_edge(_copy(state), SimConfig(), 8)[0]
        cfg = SimConfig(fingerprint=True)
        on_state, _, _, big = run_steps_edge(_copy(state), cfg, 8)
        la = jax.tree_util.tree_leaves(off_state)
        lb = jax.tree_util.tree_leaves(on_state)
        assert len(la) == len(lb)
        for x, y in zip(la, lb):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                          err_msg="fingerprint fold "
                                          "wrote the stepped state")
        assert int(np.asarray(big.steps)) == 8
        s, chainw = _copy(state), 0
        for _ in range(8):
            s, _, _, p = run_steps_edge(s, cfg, 1)
            chainw = fpmod.chain(chainw, fpmod.combine(p))
        assert chainw == fpmod.combine(big)

    def test_host_chain_and_summary(self):
        assert fpmod.chain(0, 0xDEADBEEF) == 0xDEADBEEF
        # the chain rotates: a word folded one chunk earlier lands in a
        # different position, so chunk order matters
        assert fpmod.chain(fpmod.chain(0, 1), 2) \
            != fpmod.chain(fpmod.chain(0, 2), 1)
        assert fpmod.chain(0x80000000, 0) == 1       # rotl wraps
        s = fpmod.summarize(0xBEEF, 3, 60)
        assert s == {"fp": "0000beef", "chunks": 3, "steps": 60}


# -------------------------------------------------- sim + stack commands
class TestSimFingerprint:
    @pytest.fixture(scope="class")
    def sim(self):
        from bluesky_tpu.simulation.sim import Simulation
        sim = Simulation(nmax=8)
        sim.stack.stack("CRE SDC1 B744 52 4 90 FL200 250")
        sim.stack.process()
        return sim

    def _echo(self, sim, cmd):
        sim.stack.stack(cmd)
        sim.stack.process()
        out = "\n".join(sim.scr.echobuf)
        sim.scr.echobuf.clear()
        return out

    def test_fingerprint_command_chains_a_witness(self, sim):
        assert self._echo(sim, "FINGERPRINT ON")
        assert sim.cfg.fingerprint is True
        sim.op()
        sim.fastforward()
        sim.run(until_simt=2.0, max_iters=200)
        fp = sim.fp_summary()
        assert fp is not None and fp["chunks"] >= 1
        assert len(fp["fp"]) == 8 and int(fp["fp"], 16) >= 0
        out = self._echo(sim, "FINGERPRINT")
        assert "FINGERPRINT ON" in out and fp["fp"] in out

    def test_fault_bitflip_payload_corrupts_the_word_only(self, sim):
        before = sim.fp_summary()
        chain_before = sim._fp_chain
        assert "wire corruption" in self._echo(sim,
                                               "FAULT BITFLIP PAYLOAD")
        after = sim.fp_summary()
        assert after["fp"] != before["fp"]
        # the stepped state and the device chain are untouched: only
        # the shipped witness lies (the wire-corruption injection)
        assert sim._fp_chain == chain_before
        assert sim._fp_corrupt_mask != 0
        # same bit again XORs back to clean
        self._echo(sim, "FAULT BITFLIP PAYLOAD")
        assert sim.fp_summary()["fp"] == before["fp"]

    def test_fault_bitflip_state_is_finite_guard_blind(self, sim):
        lat_before = np.asarray(sim.traf.state.ac.lat).copy()
        out = self._echo(sim, "FAULT BITFLIP STATE")
        assert "SDC1" in out and "guard-invisible" in out
        lat_after = np.asarray(sim.traf.state.ac.lat)
        assert not np.array_equal(lat_before, lat_after)
        assert np.isfinite(lat_after).all()

    def test_sdc_command_detached_readback(self, sim, monkeypatch):
        from bluesky_tpu import settings
        monkeypatch.setattr(settings, "sdc_enabled", False,
                            raising=False)
        monkeypatch.setattr(settings, "sdc_audit_rate", 0.0,
                            raising=False)
        assert "OFF" in self._echo(sim, "SDC STATUS")
        self._echo(sim, "SDC ON")
        assert settings.sdc_enabled is True
        self._echo(sim, "SDC AUDIT 0.25")
        assert settings.sdc_audit_rate == 0.25
        self._echo(sim, "SDC OFF")
        assert settings.sdc_enabled is False

    def test_unverified_v2_snapshot_load_is_surfaced(self, sim,
                                                     tmp_path):
        from bluesky_tpu.simulation import snapshot
        fname = str(tmp_path / "legacy.snap")
        with open(fname, "wb") as f:
            pickle.dump(snapshot.state_blob(sim), f)   # v2: bare pickle
        blob, err = snapshot.read_blob(fname)
        assert err is None and blob["unverified"]
        ok, msg = snapshot.load(sim, fname)
        assert ok and "UNVERIFIED" in msg
        c = sim.obs.get("snapshot_unverified")
        assert c is not None and c.value == 1


# ------------------------------------------------------- server defense
class TestSdcServer:
    def test_fp_noted_per_content_key_and_capped(self, tmp_path):
        s = _bare(tmp_path, sdc_enabled=True)
        try:
            w = make_id()
            s._note_sdc_fp(w, _piece(0), {"fp": "00000001"})
            key = BatchJournal.piece_key(_piece(0))
            assert s._sdc_fps[key] == {w.hex(): "00000001"}
            for i in range(1, 400):        # week-long sweep bound
                s._note_sdc_fp(w, _piece(i), {"fp": "00000001"})
            assert len(s._sdc_fps) <= 256
        finally:
            _close(s)

    def test_sdc_off_is_inert(self, tmp_path):
        s = _bare(tmp_path, sdc_enabled=False)
        try:
            w = make_id()
            s._note_sdc_fp(w, _piece(0), {"fp": "00000001"})
            s._sdc_compare(_piece(0))
            s._maybe_sdc_audit(w, _piece(0))
            assert not s._sdc_fps and s.sdc_suspects == 0
            assert "sdc" not in s.health_payload()
        finally:
            _close(s)

    def test_agreeing_fps_raise_nothing(self, tmp_path):
        s = _bare(tmp_path, sdc_enabled=True)
        try:
            p = _piece(0)
            s._note_sdc_fp(make_id(), p, {"fp": "0000beef"})
            s._note_sdc_fp(make_id(), p, {"fp": "0000beef"})
            s._sdc_compare(p, via="hedge_dup")
            assert s.sdc_suspects == 0
            assert not s._sdc_execs
        finally:
            _close(s)

    def test_mismatch_journals_suspect_and_dispatches_vote(self,
                                                           tmp_path):
        jpath = str(tmp_path / "m.jsonl")
        s = _bare(tmp_path, sdc_enabled=True)
        try:
            wa, wb, wc = make_id(), make_id(), make_id()
            for w in (wa, wb, wc):
                s.workers[w] = 0
                s.last_seen[w] = time.monotonic()
            s.avail_workers.append(wc)
            p = _piece(0)
            s._note_sdc_fp(wa, p, {"fp": "00000001"})
            s._note_sdc_fp(wb, p, {"fp": "00000002"})
            s._sdc_compare(p, via="hedge_dup")
            assert s.sdc_suspects == 1
            suspects, _, _ = _sdc_records(jpath)
            assert len(suspects) == 1
            assert suspects[0]["via"] == "hedge_dup"
            assert suspects[0]["fps"] == {wa.hex(): "00000001",
                                          wb.hex(): "00000002"}
            # the tie-break vote went to the FRESH idle worker
            assert s._sdc_execs[wc]["kind"] == "vote"
            assert s.inflight[wc] == p and wc not in s.avail_workers
            recs = _records(jpath)
            assert any(r["rec"] == "queued" and r.get("synthetic")
                       for r in recs)
            # a second mismatch on the same key must not re-vote
            s._sdc_compare(p, via="hedge_dup")
            assert s.sdc_suspects == 2 and len(s._sdc_execs) == 1
        finally:
            _close(s)

    def test_vote_majority_quarantines_deviant(self, tmp_path):
        jpath = str(tmp_path / "m.jsonl")
        s = _bare(tmp_path, sdc_enabled=True, mitigate_enabled=True)
        try:
            wa, wb, wc = make_id(), make_id(), make_id()
            for w in (wa, wb, wc):
                s.workers[w] = 0
                s.last_seen[w] = time.monotonic()
            s.avail_workers.append(wc)
            p = _piece(0)
            s._note_sdc_fp(wa, p, {"fp": "00000001"})
            s._note_sdc_fp(wb, p, {"fp": "00000002"})
            s._sdc_compare(p, via="hedge_dup")
            # the vote copy completes on wc, agreeing with wa
            s._note_sdc_fp(wc, p, {"fp": "00000001"})
            s._handle_server_event(s.be_event, wc, b"STATECHANGE",
                                   packb(1))
            assert s.sdc_votes == 1
            _, votes, mits = _sdc_records(jpath)
            assert len(votes) == 1 and votes[0]["deviant"] == wb.hex()
            q = [m for m in mits if m["action"] == "quarantine_worker"]
            assert len(q) == 1 and q[0]["target"] == wb.hex()
            assert q[0]["signal"] == "sdc_deviant"
            assert wb in s.sdc_quarantine
            assert s.sdc_quarantined_workers == 1
            # the exec worker itself rejoins the pool; verdict clears
            # the tracked key
            assert wc in s.avail_workers and wb not in s.avail_workers
            assert BatchJournal.piece_key(p) not in s._sdc_fps
        finally:
            _close(s)

    def test_vote_without_majority_names_nobody(self, tmp_path):
        jpath = str(tmp_path / "m.jsonl")
        s = _bare(tmp_path, sdc_enabled=True, mitigate_enabled=True)
        try:
            wa, wb, wc = make_id(), make_id(), make_id()
            for w in (wa, wb, wc):
                s.workers[w] = 0
            s.avail_workers.append(wc)
            p = _piece(0)
            s._note_sdc_fp(wa, p, {"fp": "00000001"})
            s._note_sdc_fp(wb, p, {"fp": "00000002"})
            s._sdc_compare(p, via="hedge_dup")
            s._note_sdc_fp(wc, p, {"fp": "00000003"})  # 3 distinct words
            s._handle_server_event(s.be_event, wc, b"STATECHANGE",
                                   packb(1))
            _, votes, mits = _sdc_records(jpath)
            assert len(votes) == 1 and votes[0]["deviant"] == ""
            assert not [m for m in mits
                        if m["action"] == "quarantine_worker"]
            assert not s.sdc_quarantine
        finally:
            _close(s)

    def test_quarantined_worker_never_rejoins_assignment(self,
                                                         tmp_path):
        s = _bare(tmp_path, sdc_enabled=True, mitigate_enabled=True)
        try:
            w = make_id()
            s.workers[w] = 0
            s.mitigator.on_sdc_deviant(w, _piece(0), why="test")
            assert w in s.sdc_quarantine
            # REGISTER re-add and STATECHANGE re-add both exclude it
            s._handle_server_event(s.be_event, w, b"REGISTER", b"")
            assert w not in s.avail_workers
            s._handle_server_event(s.be_event, w, b"STATECHANGE",
                                   packb(1))
            assert w not in s.avail_workers
            # MITIGATE OFF releases it (journaled RESTORING record)
            s.mitigator.set_enabled(False)
            assert not s.sdc_quarantine and w in s.avail_workers
            jpath = str(tmp_path / "m.jsonl")
            _, _, mits = _sdc_records(jpath)
            rel = [m for m in mits if m["action"] == "release_worker"]
            assert len(rel) == 1 and rel[0]["target"] == w.hex()
        finally:
            _close(s)

    def test_dead_exec_worker_never_requeues_its_piece(self, tmp_path):
        jpath = str(tmp_path / "m.jsonl")
        s = _bare(tmp_path, sdc_enabled=True)
        try:
            w = make_id()
            p = _piece(0)
            s.workers[w] = 2
            s.inflight[w] = p
            s._sdc_execs[w] = {"kind": "vote",
                               "key": BatchJournal.piece_key(p),
                               "piece": p}
            s._handle_server_event(s.be_event, w, b"STATECHANGE",
                                   packb(-1))
            # the piece is already complete: a dead vote worker must
            # not owe it back to the queue or strike it
            assert not s.scenarios and not s._sdc_execs
            assert not any(r["rec"] == "crashed"
                           for r in _records(jpath))
        finally:
            _close(s)

    def test_hedge_dup_completion_compares_fingerprints(self, tmp_path):
        """The SDCFP of a hedge LOSER lands after its piece left
        ``inflight`` — the ``_cancel_pending`` fallback must still
        record it so the dup completion can compare."""
        jpath = str(tmp_path / "m.jsonl")
        s = _bare(tmp_path, sdc_enabled=True)
        try:
            w1, w2 = make_id(), make_id()
            p = _piece(0)
            s.workers[w1] = 0
            s.workers[w2] = 2
            s._note_sdc_fp(w1, p, {"fp": "00000001"})  # winner's word
            s._cancel_pending[w2] = p
            s._handle_server_event(s.be_event, w2, b"SDCFP",
                                   packb({"fp": "00000002"}))
            s._handle_server_event(s.be_event, w2, b"STATECHANGE",
                                   packb(1))
            assert s.dup_completions == 1
            assert s.sdc_suspects == 1
            suspects, _, _ = _sdc_records(jpath)
            assert suspects and suspects[0]["via"] == "hedge_dup"
        finally:
            _close(s)

    def test_audit_sampling_accumulator(self, tmp_path):
        s = _bare(tmp_path, sdc_enabled=True, sdc_audit_rate=0.5)
        try:
            wa = make_id()
            s.workers[wa] = 0
            p = _piece(0)
            s._note_sdc_fp(wa, p, {"fp": "0000beef"})
            idle = [make_id() for _ in range(2)]
            for w in idle:
                s.workers[w] = 0
                s.avail_workers.append(w)
            # rate 0.5: fires on every SECOND eligible completion
            s._maybe_sdc_audit(wa, p)
            assert s.sdc_audits == 0
            s._maybe_sdc_audit(wa, p)
            assert s.sdc_audits == 1
            (ew,) = s._sdc_execs
            assert s._sdc_execs[ew]["kind"] == "audit"
            # the shadow copy agrees: no suspect raised
            s._note_sdc_fp(ew, p, {"fp": "0000beef"})
            s._handle_server_event(s.be_event, ew, b"STATECHANGE",
                                   packb(1))
            assert s.sdc_suspects == 0
            # a wall-clock-paced piece is never audited
            s.sdc_audit_rate = 1.0
            s.worker_progress[wa] = {"ff": False}
            s._maybe_sdc_audit(wa, p)
            assert s.sdc_audits == 1
        finally:
            _close(s)

    def test_sdc_command_sets_knobs_and_replies(self, tmp_path):
        s = _bare(tmp_path, sdc_enabled=False)
        try:
            s._handle_server_event(
                s.fe_event, b"\x01", b"SDC",
                packb({"enabled": True, "audit_rate": 0.25}))
            assert s.sdc_enabled is True
            assert s.sdc_audit_rate == 0.25
            d = s.sdc_payload()
            assert d["enabled"] and d["audit_rate"] == 0.25
            assert "SDC ON" in d["text"]
        finally:
            _close(s)

    def test_health_surfaces_sdc_and_journal_sections(self, tmp_path):
        s = _bare(tmp_path, sdc_enabled=True, mitigate_enabled=True)
        try:
            w = make_id()
            s.workers[w] = 0
            s.last_seen[w] = time.monotonic()
            s._note_progress(w, {"simt": 1.0, "chunks": 1, "state": 2,
                                 "fp": {"fp": "0000beef", "chunks": 2,
                                        "steps": 40}})
            s.mitigator.on_sdc_deviant(w, _piece(0), why="test")
            s.journal.queued_many([_piece(0)])
            h = s.health_payload()
            assert h["sdc"]["enabled"] is True
            assert h["sdc"]["quarantined_workers"] == [w.hex()]
            wf = h["workers"][w.hex()]
            assert wf["quarantined"] is True
            assert wf["fp"]["fp"] == "0000beef"
            assert h["journal"]["bytes"] > 0
            assert h["journal"]["warn"] is False
            txt = s._health_text(h)
            assert "sdc:" in txt and "journal:" in txt
            assert "SDC-QUARANTINED" in txt
            # shrink the warn line and the journal flags loud
            s.journal_warn_bytes = 1
            h = s.health_payload()
            assert h["journal"]["warn"] is True
            assert "WARN" in s._health_text(h)
        finally:
            _close(s)

    def test_replay_is_exactly_once_through_a_full_vote(self, tmp_path):
        """The whole defense leaves the queue math untouched: queued +
        completed once for the real piece, the vote copy synthetic-
        skipped, and the sdc trail surfaced."""
        jpath = str(tmp_path / "m.jsonl")
        s = _bare(tmp_path, sdc_enabled=True, mitigate_enabled=True)
        try:
            wa, wb, wc = make_id(), make_id(), make_id()
            for w in (wa, wb, wc):
                s.workers[w] = 0
            s.avail_workers.append(wc)
            p = _piece(0)
            s.journal.queued_many([p])
            s.journal.dispatched(p, wa)
            s.journal.completed(p, wa)
            s._note_sdc_fp(wa, p, {"fp": "00000001"})
            s._note_sdc_fp(wb, p, {"fp": "00000002"})
            s.journal.dup_completed(p, wb)
            s._sdc_compare(p, via="hedge_dup")
            s._note_sdc_fp(wc, p, {"fp": "00000002"})
            s._handle_server_event(s.be_event, wc, b"STATECHANGE",
                                   packb(1))
            state = BatchJournal.replay(jpath)
            assert state["pending"] == []
            assert len(state["completed"]) == 1
            assert state["synthetic_skipped"] == 1     # the vote copy
            assert len(state["sdc"]["suspects"]) == 1
            assert state["sdc"]["votes"][0]["deviant"] == wa.hex()
            assert state["sdc"]["quarantines"][0]["target"] == wa.hex()
        finally:
            _close(s)


# ------------------------------------------- closed-loop chaos (slow)
@pytest.mark.slow
def test_closed_loop_bitflip_vote_quarantine(tmp_path):
    """The ISSUE-17 acceptance case: SDC ON + hedging ON + mitigation
    ON on a live 3-worker fabric.  FAULT BITFLIP STATE corrupts one
    worker mid-piece; the shadow audit catches the fingerprint
    mismatch, the 2-of-3 vote names the deviant, the mitigation engine
    quarantines it (journaled ``mitigation`` record), and the piece
    completes journal-verified exactly-once — ZERO operator commands."""
    jpath = str(tmp_path / "sdc.jsonl")
    ev, st, wev, wst = free_ports(4)
    server = Server(headless=True,
                    ports=dict(event=ev, stream=st, wevent=wev,
                               wstream=wst),
                    spawn_workers=True, max_nnodes=3,
                    hb_interval=0.25, hb_timeout=30.0,
                    straggler_timeout=30.0, hedge_enabled=True,
                    mitigate_enabled=True, sdc_enabled=True,
                    sdc_audit_rate=1.0, journal_path=jpath)
    server.start()
    time.sleep(0.2)
    from bluesky_tpu.network.client import Client
    client = Client()
    client.connect(event_port=ev, stream_port=st, timeout=30.0)
    echoes = []
    client.event_received.connect(
        lambda n, d, s: echoes.append(str(d)) if n == b"ECHO" else None)
    try:
        server.addnodes(3)
        assert wait_for(lambda: (client.receive(10),
                                 len(server.workers) == 3)[1],
                        timeout=300), "3 real workers never registered"

        # one piece: a wall-paced window (the injection target), then
        # FF to a HOLD.  FINGERPRINT ON rides the CONTENT so every
        # redundant execution chains the same witness.
        client.send_event(b"BATCH", {
            "scentime": [0.0, 0.0, 0.0, 12.0, 150.0],
            "scencmd": ["SCEN SDCCL", "FINGERPRINT ON",
                        "CRE SDCCL B744 52 4 90 FL200 250",
                        "FF", "HOLD"]}, target=b"")
        assert wait_for(lambda: (client.receive(10),
                                 bool(server.inflight))[1],
                        timeout=120), "piece never dispatched"
        victim = next(iter(server.inflight))
        # wait for heartbeat proof the victim is INSIDE the wall-paced
        # window (aircraft created, clock advancing) — an injection
        # racing the scenario's own CRE would find no aircraft to
        # corrupt and the run would fingerprint-match cleanly
        assert wait_for(
            lambda: (client.receive(10),
                     server.worker_progress.get(victim, {})
                     .get("simt", 0.0) >= 1.5)[1],
            timeout=120), "victim never reported progress"
        # the chaos injection (NOT an operator recovery command): flip
        # one finite mantissa bit in the victim's live state mid-piece
        client.stack("FAULT BITFLIP STATE", target=victim)

        # closed loop: detect (audit mismatch) -> vote -> quarantine,
        # no further commands
        def quarantined():
            client.receive(10)
            return any(r["rec"] == "mitigation"
                       and r["action"] == "quarantine_worker"
                       for r in _records(jpath))
        assert wait_for(quarantined, timeout=600), (
            f"deviant never quarantined: {_records(jpath)} "
            f"echoes={echoes}")
        assert wait_for(lambda: (client.receive(10),
                                 not server.scenarios
                                 and not server.inflight
                                 and not server._sdc_execs)[1],
                        timeout=600), "fabric never drained"

        suspects, votes, mits = _sdc_records(jpath)
        assert suspects, "mismatch never suspected"
        assert suspects[0]["via"] in ("audit", "hedge_dup")
        assert votes and victim.hex() in votes[0]["deviant"].split(",")
        q = next(m for m in mits if m["action"] == "quarantine_worker")
        assert q["target"] == victim.hex()
        assert q["signal"] == "sdc_deviant"
        assert victim in server.sdc_quarantine
        assert victim not in server.avail_workers

        # journal-verified exactly-once: the real piece completed once;
        # the audit + vote copies are synthetic and never owed
        state = BatchJournal.replay(jpath)
        assert state["pending"] == []
        assert len(state["completed"]) == 1
        assert state["synthetic_skipped"] == 2
        assert state["sdc"]["suspects"] and state["sdc"]["votes"]
        assert state["sdc"]["quarantines"][0]["target"] == victim.hex()

        h = server.health_payload()
        assert h["sdc"]["quarantined_workers"] == [victim.hex()]
        assert h["sdc"]["votes"] >= 1
    finally:
        server.stop()
        server.join(timeout=10)
        client.close()
        for proc in server.processes:
            if proc.poll() is None:
                proc.kill()
