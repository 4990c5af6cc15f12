"""Binary state snapshots: save/restore the full device pytree + host
bookkeeping, and an in-memory snapshot ring for automatic rollback.

The reference has NO binary checkpointing — its mechanism is command-log
record/replay (SAVEIC/IC, stack.py:1185-1321), which this framework also
implements.  SURVEY §5.4 flags the true device-state snapshot as the
cheap win the reference lacks: with the whole simulation state in one
pytree, a checkpoint is one host transfer + one pickle.

Saved: every SimState array (as NumPy), the host slot tables (ids,
types), per-slot routes, and enough sim config to resume (simdt, ASAS
config, cd backend).  Restore requires a Traffic with the same nmax/wmax
(stated in the file header and checked).

Two consumers share the blob format:

* ``save``/``load`` — the SNAPSHOT SAVE/LOAD stack command (pickle file).
  ``load`` is hardened against truncated/corrupt files: any unpickling
  failure degrades to a ``(False, msg)`` command error, never an
  exception out of the stack.
* ``SnapshotRing`` — a bounded in-memory ring of periodic captures the
  integrity guard (fault/guard.py) rolls back to when a chunk trips the
  in-scan finite check.  Ring rollback restores traffic/routes/config
  but keeps stack/datalog/plugin state (``reset_traffic`` semantics, not
  the full ``reset``), so logs record the recovery instead of being
  truncated by it.

On-disk format v4 (durable runs, docs/FAULT_TOLERANCE.md):

    BSTPUSNAP4\\n <sha256-hex>\\n <shard-layout json>\\n <pickled blob>

written atomically — tmp file in the same directory, flush + fsync,
``os.replace`` onto the final name — so a crash mid-save can only leave
a stale tmp file, never a torn file under the final name.  ``load``
verifies the digest before unpickling: a bit-flipped blob that would
still unpickle (failure class #2, torn write / silent corruption) is
rejected instead of restored.  The v4 header line carries the CAPTURING
shard layout (mode, device count D, halo blocks, and in tiles mode the
R x C tile shape + pinned slab budgets) in plain JSON, so a mesh-epoch
restore onto a different device count or tile grid is detected from the
header (``peek_shard``) BEFORE the multi-hundred-MB payload is
unpickled.  v3 files (digest, no shard line) and plain-pickle v2 files
keep loading for back-compat.
"""
import collections
import dataclasses
import hashlib
import json
import os
import pickle

import numpy as np
import jax
import jax.numpy as jnp

from ..core.state import count_of_time, time_as_held

FORMAT = 4
COMPAT_FORMATS = (2, 3, 4)      # blob formats restore_blob accepts
MAGIC3 = b"BSTPUSNAP3\n"        # v3 file header (v2 = bare pickle)
MAGIC4 = b"BSTPUSNAP4\n"        # v4: + shard-layout header line
MAGIC = MAGIC3                  # back-compat alias (v3 readers)


def shard_meta(sim) -> dict:
    """The sim's active shard layout as plain-JSON metadata: rides every
    blob (and the v4 file header) so a restore onto a different device
    count / mode is detectable without touching the payload."""
    mesh = getattr(sim, "shard_mesh", None)
    meta = dict(
        mode=str(getattr(sim, "shard_mode", "off")),
        ndev=int(mesh.devices.size) if mesh is not None else 0,
        halo_blocks=int(getattr(getattr(sim, "cfg", None),
                                "cd_halo_blocks", 0) or 0),
    )
    if meta["mode"] == "tiles":
        cfg = getattr(sim, "cfg", None)
        ts = tuple(getattr(cfg, "cd_tile_shape", ()) or ())
        meta["tiles"] = [int(t) for t in ts]
        meta["tile_budgets"] = [int(b) for b in
                                getattr(cfg, "cd_tile_budgets", ())]
    return meta


def state_blob(sim, state=None) -> dict:
    """Snapshot the complete simulation state as a host-side dict.

    ``state`` overrides the device pytree to copy: the pipelined chunk
    loop passes the KEPT (non-donated) post-chunk buffers so the
    device->host copy overlaps the next in-flight chunk instead of
    blocking the dispatch.  Host tables (ids/routes/cond) are read live
    — the pipeline only defers edges with no host-table mutations, and
    the caller has collected what a plugin's own program took out of the
    passed state (``Simulation.collect_plugins``), so they match it."""
    traf = sim.traf
    if state is None:
        sim.collect_plugins()
        traf.flush()
        state = traf.state
    state_np = jax.tree.map(lambda a: np.asarray(a), state)
    routes = {i: dict(name=list(r.name), lat=list(r.lat),
                      lon=list(r.lon), alt=list(r.alt),
                      spd=list(r.spd), wtype=list(r.wtype),
                      flyby=list(r.flyby), iactwp=r.iactwp)
              for i, r in sim.routes.routes.items()}
    return dict(
        format=FORMAT,
        nmax=traf.nmax, wmax=traf.wmax,
        state=state_np,
        ids=list(traf.ids), types=list(traf.types),
        autoid=traf._autoid,
        # provenance for packed multi-world runs: which world of the
        # pack this blob captured (empty for standalone sims) — the
        # per-world preempt checkpoints carry it so operators can map
        # preempt-<id>-wNN.snap files back to their pieces
        world=sim.world_tag,
        # capturing shard layout (mode, D, halo): snapshot-ring entries
        # carry it, and write_blob lifts it into the v4 file header so
        # a cross-mesh restore is detected pre-unpickle
        shard=shard_meta(sim),
        cfg=dict(simdt=sim.cfg.simdt, cd_backend=sim.cfg.cd_backend,
                 asas=sim.cfg.asas._asdict()),
        dtmult=sim.dtmult,
        routes=routes,
        # pending ATALT/ATSPD conditions are traffic-scoped state: both
        # restore paths reset them, so they must ride the blob or a
        # rollback silently disarms every deferred command
        cond=dict(idx=np.asarray(sim.cond.idx),
                  condtype=np.asarray(sim.cond.condtype),
                  target=np.asarray(sim.cond.target),
                  lastdif=np.asarray(sim.cond.lastdif),
                  cmd=list(sim.cond.cmd)),
    )


def _counted(state, simdt):
    """``state`` as a blob holds it, with its step count: a state
    written before the state counted its steps (its clock a sum of
    ``simdt`` steps) gets the count nearest its time."""
    if hasattr(state, "nstep"):
        return state
    held = {f.name: getattr(state, f.name)
            for f in dataclasses.fields(state) if f.name != "nstep"}
    nstep = count_of_time(float(held["simt"]), simdt)
    held["simt"] = np.asarray(time_as_held(
        nstep * simdt, held["simt"].dtype), held["simt"].dtype)
    return type(state)(nstep=np.asarray(nstep, np.int32), **held)


def restore_blob(sim, blob, full_reset: bool = True):
    """Restore a state blob into the running simulation.

    ``full_reset=False`` is the rollback path: only traffic-scoped state
    is cleared (``reset_traffic``), so datalog/stack/plugin state — and
    with it the record of the fault that triggered the rollback —
    survives the restore.
    """
    if blob.get("format") not in COMPAT_FORMATS:
        return False, "unsupported snapshot format"
    traf = sim.traf
    if blob["nmax"] != traf.nmax or blob["wmax"] != traf.wmax:
        return False, (f"snapshot is nmax={blob['nmax']}/"
                       f"wmax={blob['wmax']}; this sim is "
                       f"nmax={traf.nmax}/wmax={traf.wmax}")
    if full_reset:
        sim.reset()
    else:
        sim.reset_traffic()
    traf = sim.traf
    # Device state: same treedef, arrays re-uploaded with current dtypes
    old_table = traf.state.asas.partners_s
    traf.state = jax.tree.map(
        lambda old, new: jnp.asarray(new, old.dtype),
        traf.state, _counted(blob["state"], blob["cfg"]["simdt"]))
    # Cross-shard-mode blobs: the sorted-space caches (sort_perm, the
    # partner table) are keyed to the CAPTURING mode's padded layout.
    # Adopting a spatial/tiles-mode layout into a sim whose tables are
    # sized differently would silently drop top-stripe aircraft from
    # the sparse schedule (their sorted slots land past the smaller
    # layout's row count and the padded scatter runs in drop mode) —
    # and the reset above rebuilt DEFAULT-size tables, which are too
    # small for an active spatial/tiles layout.  Size the caches to
    # what the RUNNING sim's mode expects — identity sort (the
    # known-good stale layout; reachability is rebuilt from true
    # positions every interval) and an empty partner table — and force
    # a re-sort before the next chunk whenever the blob's layout is
    # not the running one.
    from ..core.state import SORT_PAD
    kk = old_table.shape[1]
    if getattr(sim, "shard_mode", "off") in ("spatial", "tiles") \
            and getattr(sim, "shard_mesh", None) is not None:
        from ..core.asas import spatial_table_size
        n_exp = spatial_table_size(
            traf.nmax, min(sim.cfg.cd_block, 256),
            int(sim.shard_mesh.devices.size))
    else:
        n_exp = traf.nmax + SORT_PAD
    if traf.state.asas.partners_s.shape[0] != n_exp:
        traf.state = traf.state.replace(asas=traf.state.asas.replace(
            sort_perm=jnp.arange(traf.nmax, dtype=jnp.int32),
            partners_s=jnp.full((n_exp, kk), -1, jnp.int32)))
        sim._invalidate_sort()
    # Cross-MESH blobs (mesh-epoch recovery): a blob captured at a
    # different device count or shard mode carries stripe bucketing
    # keyed to the CAPTURING mesh even when the table shapes happen to
    # match.  The shard metadata makes the mismatch explicit: reset the
    # sorted-space caches to the known-good identity layout and force
    # the full re-sort/re-bucket + conservative halo re-validation
    # before the next chunk.
    bshard = blob.get("shard")
    if bshard is not None:
        cur = shard_meta(sim)
        if (bshard.get("ndev"), bshard.get("mode"),
                bshard.get("tiles")) \
                != (cur["ndev"], cur["mode"], cur.get("tiles")):
            traf.state = traf.state.replace(asas=traf.state.asas.replace(
                sort_perm=jnp.arange(traf.nmax, dtype=jnp.int32),
                partners_s=jnp.full_like(traf.state.asas.partners_s,
                                         -1)))
            sim._invalidate_sort()
    # Restore under an active mesh: re-place the (host-restored) arrays
    # with the mode's canonical shardings, and in spatial mode force a
    # re-bucketing refresh before the next chunk — the restored
    # stripe layout is internally consistent (it was captured with its
    # sort_perm/partner tables), but its drift-margin clock is unknown,
    # so the conservative halo re-validation must run first.
    if getattr(sim, "shard_mesh", None) is not None \
            and getattr(sim, "shard_mode", "off") != "off":
        from ..parallel import sharding as shd
        sh = shd.spatial_state_shardings(traf.state, sim.shard_mesh) \
            if sim.shard_mode in ("spatial", "tiles") \
            else shd.state_shardings(traf.state, sim.shard_mesh)
        traf.state = jax.tree.map(lambda x, s: jax.device_put(x, s),
                                  traf.state, sh)
        sim._invalidate_sort()
    # a callsign the blob pairs with a slot its state has inactive is
    # of an aircraft a program had taken out and the host had not read
    # yet when the blob was written: nobody would ever forget it here
    held = np.asarray(blob["state"].ac.active)
    traf.ids = [i if on else None for i, on in zip(blob["ids"], held)]
    traf.types = [t if on else None for t, on in zip(blob["types"], held)]
    traf.epoch += 1        # another fleet than the one before the restore
    traf._id2slot = {acid: i for i, acid in enumerate(traf.ids)
                     if acid is not None}
    traf._autoid = blob["autoid"]
    # Host route tables
    for i, r in blob.get("routes", {}).items():
        if not held[int(i)]:
            continue
        hr = sim.routes.route(int(i))
        hr.name = list(r["name"])
        hr.lat = list(r["lat"])
        hr.lon = list(r["lon"])
        hr.alt = list(r["alt"])
        hr.spd = list(r["spd"])
        hr.wtype = list(r["wtype"])
        hr.flyby = list(r["flyby"])
        hr.iactwp = r["iactwp"]
    # Pending conditional commands (absent in blobs saved before they
    # were captured: nothing to restore then)
    cond = blob.get("cond")
    if cond is not None:
        sim.cond.idx = np.asarray(cond["idx"], dtype=np.int64)
        sim.cond.condtype = np.asarray(cond["condtype"], dtype=np.int64)
        sim.cond.target = np.asarray(cond["target"], dtype=np.float64)
        sim.cond.lastdif = np.asarray(cond["lastdif"], dtype=np.float64)
        sim.cond.cmd = list(cond["cmd"])
    # Config
    from ..core.asas import AsasConfig
    cfg = blob["cfg"]
    sim.cfg = sim.cfg._replace(simdt=cfg["simdt"],
                               cd_backend=cfg["cd_backend"],
                               asas=AsasConfig(**cfg["asas"]))
    sim.dtmult = blob["dtmult"]
    return True, (f"restored: {traf.ntraf} aircraft "
                  f"at simt={sim.simt:.2f}")


def write_blob(blob, fname):
    """Atomically persist a state blob: tmp file + fsync + rename.

    The tmp file lives in the destination directory (``os.replace``
    must not cross filesystems); any failure removes it, so the final
    name only ever holds a complete, checksummed snapshot — a previous
    good file survives a failed re-save untouched.  Raises ``OSError``
    on disk-full/bad-path; callers degrade to a command error.
    """
    payload = pickle.dumps(blob, protocol=pickle.HIGHEST_PROTOCOL)
    digest = hashlib.sha256(payload).hexdigest().encode("ascii")
    shard_line = json.dumps(
        blob.get("shard") or dict(mode="off", ndev=0, halo_blocks=0),
        sort_keys=True).encode("ascii")
    tmp = f"{fname}.tmp.{os.getpid()}"
    try:
        with open(tmp, "wb") as f:
            f.write(MAGIC4 + digest + b"\n" + shard_line + b"\n"
                    + payload)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, fname)
    except OSError:
        try:
            os.remove(tmp)
        except OSError:
            pass
        raise
    return fname


def save(sim, fname):
    """Write an atomic, checksummed snapshot of the complete simulation
    state (format v4).  Raises ``OSError`` on disk-full/bad path — the
    SNAPSHOT stack command catches it and degrades to a command error,
    symmetric with the hardened ``load``."""
    return write_blob(state_blob(sim), fname)


def _split_v4(raw):
    """Split a v4 byte stream into (digest, shard_meta, payload) —
    raises on a malformed header (caught by the callers' hardening)."""
    digest_end = raw.index(b"\n", len(MAGIC4))
    digest = raw[len(MAGIC4):digest_end].decode("ascii")
    shard_end = raw.index(b"\n", digest_end + 1)
    shard = json.loads(raw[digest_end + 1:shard_end].decode("ascii"))
    if not isinstance(shard, dict):
        raise ValueError("shard header is not a JSON object")
    return digest, shard, raw[shard_end + 1:]


def peek_shard(fname):
    """Surface a v4 snapshot's shard-layout header WITHOUT unpickling:
    ``(shard_dict, None)`` for v4 files, ``(None, None)`` for
    pre-shard-header formats (v2/v3 — readable, layout unknown), or
    ``(None, errmsg)`` on an unreadable/malformed file.  The mesh-epoch
    restore path uses this to detect a D/mode mismatch from the header
    instead of after unpickling the payload."""
    try:
        with open(fname, "rb") as f:
            head = f.read(64 * 1024)
        if not head.startswith(MAGIC4):
            return None, None
        _, shard, _ = _split_v4(head)
        return shard, None
    except (OSError, ValueError, UnicodeDecodeError) as exc:
        return None, (f"corrupt or truncated snapshot header "
                      f"({type(exc).__name__}: {exc})")


def read_blob(fname):
    """Read + verify a snapshot file; returns ``(blob, None)`` or
    ``(None, errmsg)``.  v3/v4 files are checksum-verified BEFORE
    unpickling, so a bit-flipped payload that would still unpickle is
    rejected; v4 files additionally surface the shard-layout header
    into ``blob["shard"]``; files without a magic fall back to the v2
    plain pickle for back-compat.  A v2 load carries NO integrity
    check — the returned blob is tagged ``blob["unverified"]`` so the
    restore path can surface it (the SDC defense treats an unverified
    restore as a corruption blind spot, docs/FAULT_TOLERANCE.md)."""
    hdr_shard = None
    unverified = None
    try:
        with open(fname, "rb") as f:
            raw = f.read()
        if raw.startswith(MAGIC4):
            digest, hdr_shard, payload = _split_v4(raw)
            if hashlib.sha256(payload).hexdigest() != digest:
                return None, ("corrupt or truncated snapshot "
                              "(checksum mismatch)")
            blob = pickle.loads(payload)
        elif raw.startswith(MAGIC3):
            header_end = raw.index(b"\n", len(MAGIC3))
            digest = raw[len(MAGIC3):header_end].decode("ascii")
            payload = raw[header_end + 1:]
            if hashlib.sha256(payload).hexdigest() != digest:
                return None, ("corrupt or truncated snapshot "
                              "(checksum mismatch)")
            blob = pickle.loads(payload)
        else:
            blob = pickle.loads(raw)        # v2: bare pickle, no digest
            unverified = "legacy v2 plain pickle, no checksum"
    except (OSError, EOFError, pickle.UnpicklingError, AttributeError,
            MemoryError, ImportError, IndexError, KeyError,
            UnicodeDecodeError, ValueError) as exc:
        return None, (f"corrupt or truncated snapshot "
                      f"({type(exc).__name__}: {exc})")
    if not isinstance(blob, dict) \
            or blob.get("format") not in COMPAT_FORMATS:
        return None, "unsupported snapshot format"
    if hdr_shard is not None:
        blob.setdefault("shard", hdr_shard)
    if unverified:
        blob["unverified"] = unverified
    return blob, None


def load(sim, fname):
    """Restore a snapshot into the running simulation.

    Robust to damaged files: a truncated, bit-flipped or corrupt
    snapshot (the FAULT SNAPTRUNC chaos case) returns a command error
    instead of raising out of the stack.
    """
    blob, err = read_blob(fname)
    if blob is None:
        return False, f"{fname}: {err}"
    unverified = blob.get("unverified")
    if unverified:
        # A restore with no checksum is a silent-corruption blind spot:
        # count it and journal a trace record so an operator (or the SDC
        # audit) can tell which runs started from unvouched state.
        sim.obs.counter(
            "snapshot_unverified",
            help="snapshot restores with no checksum verification").inc()
        sim.recorder.instant("snapshot_unverified", cat="fault",
                             file=str(fname), why=str(unverified))
    ok, msg = restore_blob(sim, blob)
    if ok and unverified:
        msg += (f" [UNVERIFIED: {unverified} — SNAPSHOT SAVE rewrites "
                f"it as v{FORMAT} with a digest]")
    return ok, (f"Snapshot {fname} {msg}" if ok else f"{fname}: {msg}")


class SnapshotRing:
    """Bounded in-memory ring of periodic state snapshots.

    ``maybe_capture`` is called by the sim at chunk edges and captures
    every ``dt`` seconds of sim time (depth * dt is the rollback
    horizon).  ``rollback`` restores the newest snapshot with
    traffic-scoped reset semantics and POPS it from the ring, so a fault
    that recurs immediately degrades to progressively older snapshots
    instead of looping on one restore point forever.
    """

    def __init__(self, depth: int = 4, dt: float = 30.0):
        self.depth = max(1, int(depth))
        self.dt = float(dt)
        self._ring = collections.deque(maxlen=self.depth)
        self.t_last = -float("inf")

    def __len__(self):
        return len(self._ring)

    @property
    def simts(self):
        """Sim times of the held snapshots, oldest first."""
        return [float(np.asarray(b["state"].simt)) for b in self._ring]

    def capture(self, sim, state=None, simt=None):
        """Capture now.  ``state``/``simt`` let the pipelined loop hand
        in the kept post-chunk buffers + planned edge clock so the copy
        overlaps the in-flight chunk (no device sync here)."""
        with sim.timed("snapshot_capture", "sim_snapshot_capture_ms",
                       world=sim.world_tag, off_path=state is not None):
            self._ring.append(state_blob(sim, state=state))
        self.t_last = sim.simt if simt is None else float(simt)

    def newest(self):
        """The most recent snapshot blob, or None (the autosnapshot
        path persists this entry to disk without consuming it)."""
        return self._ring[-1] if self._ring else None

    def maybe_capture(self, sim):
        """Capture if ``dt`` sim seconds have passed since the last one."""
        if self.dt > 0 and sim.simt - self.t_last >= self.dt - 1e-9:
            self.capture(sim)

    def rollback(self, sim):
        """Restore (and consume) the newest snapshot; (ok, msg)."""
        if not self._ring:
            return False, "snapshot ring is empty"
        blob = self._ring.pop()
        ok, msg = restore_blob(sim, blob, full_reset=False)
        self.t_last = sim.simt
        return ok, msg

    def clear(self):
        self._ring.clear()
        self.t_last = -float("inf")
