"""Check kind "flow" (one world whose fleet turns over, its frames
consumed): the frames of check "interval", over the aircraft both of a
pair hold, and what creating and deleting add.

``interval_*``: ``checks/interval.py`` over at most ``spec["pairs"]``
pairs of frames at least one CD interval apart (a free-running world of
2-step chunks sends a frame every few tenths of a simulated second, and
a pair costs the reference ten seconds at N=100,000).

Over every frame the client kept, against ``reference/flow.py`` flying
each aircraft straight on from an earlier frame:

``flow_left_not_deleted``: aircraft inside the circle in frame A that
the reference has outside it, by more than its slack, from more than an
AREA period and a chunk before frame B on, and that frame B still holds.
``flow_deleted_inside``: aircraft of a frame that the next frame lacks,
inside the circle in the one and, by the reference, still inside by
more than its slack at the other.
``flow_ids_reused``: callsigns a frame lacks that an earlier and a later
frame hold.  All three: limit 0.
"""
import numpy as np

from . import frames as fr
from . import interval


def _apart(frames, spec, ref, clock):
    """Frames at least one CD interval of steps apart, ``pairs`` + 1 at
    most, from the first on."""
    interval = int(round(float(spec["cd_interval_s"]) / ref.SIMDT))
    out = [frames[0]]
    for f in frames[1:]:
        if len(out) <= int(spec["pairs"]) and ref.steps_at(
                float(f["simt"]), clock) >= ref.steps_at(
                float(out[-1]["simt"]), clock) + interval:
            out.append(f)
    return out


def _steps(a, b, evidence, ref, clock):
    """Steps between two frames: whole chunks of the probe's programs,
    by the steps the frames' clock made."""
    chunk = int(round(float(evidence["chunk_sim_s"]) / ref.SIMDT))
    return chunk * int(round((ref.steps_at(float(b["simt"]), clock)
                              - ref.steps_at(float(a["simt"]), clock))
                             / chunk))


def flow_numbers(spec, evidence, ref):
    frames = fr.frames_of(evidence, ref)
    clock = frames[0]["clock"]
    circle = spec["circle"]
    chunk = int(round(float(evidence["chunk_sim_s"]) / ref.SIMDT))
    # the steps by which a leaver has met an AREA tick: a period and a
    # chunk (the tick runs on a chunk edge)
    settle = int(round(float(spec["area_dt_s"]) / ref.SIMDT)) + chunk
    where = [{acid: k for k, acid in enumerate(f["id"])} for f in frames]
    last, reused = {}, 0
    not_deleted = deleted_inside = judged = 0
    for k, b in enumerate(frames):
        for acid in b["id"]:
            if last.get(acid, k - 1) < k - 1:
                reused += 1
            last[acid] = k
        if k == 0:
            continue
        # who left between the last frame and this one
        a = frames[k - 1]
        gone = np.asarray([i for i, acid in enumerate(a["id"])
                           if acid not in where[k]], dtype=int)
        if len(gone):
            nst = _steps(a, b, evidence, ref, clock)
            now, then = ref.leaves(a, gone, nst, circle)
            slack = ref.slack_m(a, gone, nst)
            deleted_inside += int(((now < -slack) & (then < -slack)).sum())
        # who should have: from the newest frame that lies a settling
        # time and a little more before this one
        early = [f for f in frames[:k]
                 if _steps(f, b, evidence, ref, clock) >= settle + chunk]
        if early:
            a = early[-1]
            both = np.asarray([i for i, acid in enumerate(a["id"])
                               if acid in where[k]], dtype=int)
            nst = _steps(a, b, evidence, ref, clock) - settle
            now, then = ref.leaves(a, both, nst, circle)
            slack = ref.slack_m(a, both, nst)
            not_deleted += int(((now < -slack) & (then > slack)).sum())
            judged += len(both)
    return {"flow_left_not_deleted": float(not_deleted),
            "flow_deleted_inside": float(deleted_inside),
            "flow_ids_reused": float(reused),
            "flow_frames": float(len(frames)),
            "flow_judged": float(judged)}


def numbers(spec, evidence, seed, ref):
    out = interval.numbers(
        spec, dict(evidence, frames=_apart(evidence["frames"], spec, ref,
                                           fr.clock_of(evidence, ref))),
        seed, ref)
    out.update(flow_numbers(spec, evidence, ref))
    return out


def control_evidence(spec, evidence, seed, ref):
    """The same evidence with the reference in bfloat16 standing where
    the program stood.  In the frames ``interval`` compares: flags and
    vectors of the ownships it samples, and the positions of those flown
    on from the frame before, recomputed in bfloat16; the flag of every
    other aircraft cleared, so that the comparison samples no ownship
    the control did not compute (all of a saturated fleet's flagged
    ownships against 100,000 is 48 GB of pairs).  In the last frame,
    membership decided in bfloat16: aircraft it has outside the circle
    deleted, aircraft of the frame a settling time earlier that the
    program deleted and it has inside put back, flown straight on."""
    q = ref.Precision("bfloat16")
    frames = [dict(f) for f in evidence["frames"]]
    place = {id(f): k for k, f in enumerate(evidence["frames"])}
    clock = fr.clock_of(evidence, ref)
    apart = _apart(evidence["frames"], spec, ref, clock)
    sub = dict(evidence, frames=apart)
    for a, b, own, ob, nst, cob, kb in interval.pairs(spec, sub, seed, ref):
        out = frames[place[id(apart[kb])]]
        every = np.union1d(ob, cob)
        inconf, ase, asn = ref.interval_of_sample(every, b, q)
        lat, lon = ref.fly(a, b, own, ob, nst, q)
        out["inconf"] = np.zeros(len(b["id"]), bool)
        out["inconf"][every] = inconf
        for key, val, at in (("asase", ase, every), ("asasn", asn, every),
                             ("lat", lat, ob), ("lon", lon, ob)):
            out[key] = np.array(b[key])
            out[key][at] = val
    # membership of the last frame, decided in bfloat16
    circle = spec["circle"]
    chunk = int(round(float(evidence["chunk_sim_s"]) / ref.SIMDT))
    settle = int(round(float(spec["area_dt_s"]) / ref.SIMDT)) + chunk
    b = fr.frame_arrays(frames[-1])
    early = [f for f in evidence["frames"][:-1]
             if _steps(f, b, evidence, ref, clock) >= settle + chunk]
    keep = ref.outside_m(circle, b["lat"], b["lon"], q) <= 0
    cols = {key: np.asarray(frames[-1][key])[keep]
            for key in frames[-1] if key not in ("id", "simt")
            and np.ndim(frames[-1][key]) == 1
            and len(frames[-1][key]) == len(b["id"])}
    ids = [acid for acid, k in zip(b["id"], keep) if k]
    if early:
        a = fr.frame_arrays(early[-1])
        held = set(b["id"])
        gone = np.asarray([i for i, acid in enumerate(a["id"])
                           if acid not in held], dtype=int)
        nst = _steps(a, b, evidence, ref, clock)
        lat, lon = ref.straight_on(a, gone, nst, q)
        back = gone[ref.outside_m(circle, lat, lon, q) <= 0]
        lat, lon = ref.straight_on(a, back, nst)
        for key in cols:
            add = lat if key == "lat" else lon if key == "lon" \
                else np.asarray(early[-1][key])[back] \
                if key in early[-1] else np.zeros(len(back))
            cols[key] = np.concatenate([cols[key], add.astype(
                cols[key].dtype)])
        ids += [a["id"][i] for i in back]
    frames[-1] = dict(frames[-1], id=ids, **cols)
    return dict(evidence, frames=frames)
