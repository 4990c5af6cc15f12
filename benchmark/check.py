"""The comparison that decides ``correct``: what the timed path produced,
as the client received it, against the plain reference.

``decide(spec, evidence, seed)`` returns ``(correct, numbers, also)``:
``numbers`` maps each number compared to ``{"value", "limit"}``, ``also``
holds what else was read and is held to nothing.  A run
is correct when every number the mix compares was read and none is over
its limit.  ``control_evidence`` puts the reference, computed in
bfloat16, in the program's place: the evidence it returns has to come
out as not correct (tests/test_control.py; ``run.py --control 1`` on the
chip).

kind "frames" (one world, streamed): two ACDATA frames one chunk of the
  mix's own programs apart (1000 steps in fast-forward), as the client
  received them.  The flags and MVP resolution vectors of frame B come
  from a detection up to one interval before B, on a state no client
  sees: the reference detects and resolves, for a seeded sample of
  ownships against all aircraft, on B flown back to that step on its
  own velocity, an approximation, with the wider limits that earns.
  Aircraft that kept their velocity over the chunk flew straight and are
  held to BlueSky's position update step by step; the others, which the
  autopilot and MVP turned, are held coarsely to the mean of the two
  frames' velocities.

kind "pieces" (a batch of small worlds): a seeded sample of the pieces
  the window finished, each stepped by the reference from the piece's
  own lines to the time of its last commands, against the positions the
  piece echoed there; and the journal's word that no piece completed
  twice or crashed.
"""
import json
import sys

import numpy as np

from reference import plain


def _sample(n, k, seed):
    rng = np.random.default_rng([int(seed), 77])
    return np.sort(rng.choice(n, size=min(k, n), replace=False))


def _frame_arrays(frame):
    return {k: np.asarray(frame[k], np.float32)
            for k in ("lat", "lon", "alt", "trk", "gs", "vs",
                      "asase", "asasn")} | {
        "inconf": np.asarray(frame["inconf"], bool),
        "simt": float(frame["simt"]), "id": list(frame["id"])}


def _steps_since_detection(simt, nmax):
    """The program detects at the first step whose float32 clock has
    reached the next whole second, and the clock is a float32 sum of
    0.05 s steps, which runs 0.02% slow below 1024 s and 0.1% fast above
    it: the instant drifts through the chunk.  Replays the clock back
    from a chunk edge at ``simt``: the steps since the last detection."""
    f32, dt = np.float32, np.float32(plain.SIMDT)
    inc = float(f32(f32(simt) + dt) - f32(simt))      # a step of the clock
    last = float(f32(simt)) - inc                     # the last step's start
    return min(nmax, int((last - np.floor(last)) / inc) + 1)


def _pairs(spec, evidence, seed):
    """Consecutive frames with the same fleet: (A, B, the sampled
    ownships' places in A and in B, the steps between the frames, and
    the places in B of a second sample, of the ownships B flags as in
    conflict: two in a hundred aircraft are, too few of a plain sample
    to hold the resolution to anything)."""
    frames = [_frame_arrays(f) for f in evidence["frames"]]
    chunk_s = float(evidence["chunk_sim_s"])
    chunk_steps = int(round(chunk_s / plain.SIMDT))
    for a, b in zip(frames[:-1], frames[1:]):
        n = len(a["id"])
        if len(b["id"]) != n:
            continue
        pos_b = {acid: k for k, acid in enumerate(b["id"])}
        own = _sample(n, int(spec["sample"]), seed)
        ob = np.asarray([pos_b[a["id"][i]] for i in own])
        # whole chunks between the frames, by the mix's chunk length: the
        # frames' own clock is the drifting one
        nst = chunk_steps * max(1, int(round((b["simt"] - a["simt"])
                                             / chunk_s)))
        flagged = np.flatnonzero(b["inconf"])
        yield a, b, own, ob, nst, flagged[_sample(
            len(flagged), int(spec["conflict_sample"]), seed)]


def _flown_back(b, steps):
    """Frame B flown back ``steps`` steps on its own velocity."""
    back_s = np.float32(plain.SIMDT * steps)
    back = dict(b)
    hr = np.radians(b["trk"])
    back["lat"] = b["lat"] - np.degrees(
        back_s * b["gs"] * np.cos(hr) / np.float32(plain.REARTH))
    back["lon"] = b["lon"] - np.degrees(
        back_s * b["gs"] * np.sin(hr)
        / np.cos(np.radians(b["lat"])) / np.float32(plain.REARTH))
    back["alt"] = b["alt"] - back_s * b["vs"]
    return back


def frames_numbers(spec, evidence, seed):
    q = plain.Precision()
    chunk_steps = int(round(float(evidence["chunk_sim_s"]) / plain.SIMDT))
    acc = {k: [] for k in ("n", "flag_miss", "reso_gap", "conf_seen",
                           "conf_sampled",
                           "steady_n", "steady_gap", "steady_flag_miss",
                           "turned_gap", "conf_n", "conf_steady")}
    for a, b, own, ob, nst, cob in _pairs(spec, evidence, seed):
        # flags and vectors of B: detected some steps before B
        back = _flown_back(b, _steps_since_detection(b["simt"],
                                                     chunk_steps))
        inconf, _, _ = plain.interval_of_sample(ob, back, q)
        miss = inconf != b["inconf"][ob]
        acc["n"].append(len(ob))
        acc["flag_miss"].append(int(miss.sum()))
        if len(cob):
            both, ase, asn = plain.interval_of_sample(cob, back, q)
            acc["reso_gap"].append(np.hypot(ase - b["asase"][cob],
                                            asn - b["asasn"][cob])[both])
            acc["conf_seen"].append(int(both.sum()))
            acc["conf_sampled"].append(len(cob))
        # aircraft that kept their velocity over the chunk flew
        # straight: BlueSky's position update, step by step
        steady = (a["trk"][own] == b["trk"][ob]) \
            & (a["gs"][own] == b["gs"][ob]) \
            & (a["vs"][own] == 0) & (b["vs"][ob] == 0)
        acc["steady_n"].append(int(steady.sum()))
        acc["steady_flag_miss"].append(int(miss[steady].sum()))
        if steady.any():
            acc["steady_gap"].append(plain.dead_reckon(
                a, b, own[steady], ob[steady], nst, q))
        if (~steady).any():
            acc["turned_gap"].append(plain.dead_reckon(
                a, b, own[~steady], ob[~steady], nst, q))
        conf = a["inconf"][own] & b["inconf"][ob]
        acc["conf_n"].append(int(conf.sum()))
        acc["conf_steady"].append(int((conf & steady).sum()))
    out = {}
    n = sum(acc["n"])
    if not n:
        return out
    out["chunk_flag_mismatch_share"] = sum(acc["flag_miss"]) / n
    out["chunk_unsteady_share"] = 1.0 - sum(acc["steady_n"]) / n
    dv = np.concatenate(acc["reso_gap"] or [np.zeros(0)])
    if len(dv):
        # the ownships in conflict by both: the gap [m/s] between the
        # resolution vector B carries and the reference's MVP
        out["chunk_reso_gap_p50_ms"] = float(np.percentile(dv, 50))
        out["chunk_reso_gap_p90_ms"] = float(np.percentile(dv, 90))
        out["chunk_reso_compared"] = float(len(dv))
        # of the ownships B flags, the share the reference flags too
        out["chunk_flagged_confirmed_share"] = sum(acc["conf_seen"]) \
            / sum(acc["conf_sampled"])
    if acc["steady_gap"]:
        out["chunk_position_gap_p99_m"] = float(np.percentile(
            np.concatenate(acc["steady_gap"]), 99))
        out["chunk_steady_flag_mismatch_share"] = \
            sum(acc["steady_flag_miss"]) / sum(acc["steady_n"])
    if acc["turned_gap"]:
        g = np.concatenate(acc["turned_gap"])
        out["chunk_turned_position_gap_p90_m"] = float(np.percentile(g, 90))
    if sum(acc["conf_n"]):
        out["chunk_conflict_steady_share"] = sum(acc["conf_steady"]) \
            / sum(acc["conf_n"])
    return out


def _step_pieces(pieces, q, nmarks):
    """The pieces stepped by the reference, each alone in a world of its
    own (one array, no pair across worlds); returns, for each of the
    first ``nmarks`` marks, the positions of all their aircraft.  A
    mark's commands run at the first step at or after its time, on the
    float32 clock of the simulation."""
    ac = [a for p in pieces for a in p["aircraft"]]
    world = [k for k, p in enumerate(pieces) for _ in p["aircraft"]]
    col = lambda k: [a[k] for a in ac]          # noqa: E731
    st = plain.new_fleet(col("lat"), col("lon"), col("hdg"), col("alt_m"),
                         col("cas_ms"), col("sel_hdg"), col("sel_cas_ms"),
                         world, q)
    at = []
    for t in pieces[0]["marks_s"][:nmarks]:
        while st["simt"] < np.float32(t):
            plain.step(st, q)
        at.append((st["lat"].copy(), st["lon"].copy()))
    return at


def pieces_numbers(spec, evidence, seed):
    """``states``: {(piece, mark): {acid: (lat, lon)}} as the pieces
    echoed them.  Every echo of every mark has to be there.  For each of
    the first ``follow_marks`` marks, as far as a reference can follow a
    piece (PERF.md), the gaps [m] between the echoed positions and the
    reference's, over the echoed aircraft of the sampled pieces: their
    median, 90th percentile and largest, and the median over the pieces
    of a piece's worst aircraft (a piece whose dynamics took another
    turn on a rounding takes all its aircraft along, so the aircraft of
    one piece are one draw, not eight)."""
    pieces, states = evidence["pieces"], evidence["states"]
    out = {"pieces_not_once": float(evidence["duplicates"])}
    if not pieces:
        return out
    pick = [pieces[k] for k in _sample(len(pieces), int(spec["sample"]),
                                       seed)]
    nmarks = int(spec["follow_marks"])
    gaps, worst = [[] for _ in range(nmarks)], {}
    missing = sum(1 for p in pieces for m in range(len(p["marks_s"]))
                  for a in p["aircraft"] if a["echoed"][m]
                  and a["id"] not in states.get((p["name"], m), {}))
    for m, (lat, lon) in enumerate(_step_pieces(pick, plain.Precision(),
                                                nmarks)):
        i = 0
        for p in pick:
            got = states.get((p["name"], m), {})
            for a in p["aircraft"]:
                if a["echoed"][m] and a["id"] in got:
                    la, lo = got[a["id"]]
                    dn = np.radians(la - float(lat[i])) * plain.REARTH
                    de = np.radians(lo - float(lon[i])) * plain.REARTH \
                        * np.cos(np.radians(la))
                    gaps[m].append(float(np.hypot(dn, de)))
                    key = (m, p["name"])
                    worst[key] = max(worst.get(key, 0.0), gaps[m][-1])
                i += 1
    out["mark_states_missing"] = float(missing)
    for m, g in enumerate(gaps):
        if g:
            out[f"mark{m}_position_gap_p50_m"] = float(np.percentile(g, 50))
            out[f"mark{m}_position_gap_p90_m"] = float(np.percentile(g, 90))
            out[f"mark{m}_position_gap_max_m"] = max(g)
            out[f"mark{m}_median_piece_worst_gap_m"] = float(np.median(
                [v for (mm, _), v in worst.items() if mm == m]))
    return out


KINDS = {"frames": frames_numbers, "pieces": pieces_numbers}


def control_evidence(spec, evidence, seed):
    """The same evidence with the reference in bfloat16 standing where
    the program stood: for frames, what frame B carries for the sampled
    ownships recomputed in bfloat16 (flags and resolution vectors from
    B's own state, positions flown on from frame A); for pieces, the
    positions at every followed mark of the sampled pieces stepped in
    bfloat16."""
    q = plain.Precision("bfloat16")
    ev = dict(evidence)
    if spec["kind"] == "frames":
        frames = [dict(f) for f in evidence["frames"]]
        for (a, b, own, ob, nst, _), out in zip(
                _pairs(spec, evidence, seed), frames[1:]):
            # flags and vectors: the sampled ownships and every one B
            # flags, so that whichever the comparison samples is bfloat16
            every = np.union1d(ob, np.flatnonzero(b["inconf"]))
            inconf, ase, asn = plain.interval_of_sample(every, b, q)
            lat, lon = plain.fly(a, b, own, ob, nst, q)
            for key, val, at in (("inconf", inconf, every),
                                 ("asase", ase, every),
                                 ("asasn", asn, every),
                                 ("lat", lat, ob), ("lon", lon, ob)):
                out[key] = np.array(b[key])
                out[key][at] = val
        ev["frames"] = frames
    else:
        states = dict(evidence["states"])
        pieces = evidence["pieces"]
        pick = [pieces[k] for k in _sample(len(pieces),
                                           int(spec["sample"]), seed)]
        for m, (lat, lon) in enumerate(_step_pieces(
                pick, q, int(spec["follow_marks"]))):
            i = 0
            for p in pick:
                states[(p["name"], m)] = {
                    a["id"]: (float(lat[i + k]), float(lon[i + k]))
                    for k, a in enumerate(p["aircraft"])}
                i += len(p["aircraft"])
        ev["states"] = states
    return ev


def decide(spec, evidence, seed):
    got = KINDS[spec["kind"]](spec, evidence, seed)
    wanted = evidence.get("compares") or list(spec["limits"])
    numbers, correct = {}, True
    for name in wanted:
        limit = spec["limits"][name]
        value = got.get(name)
        numbers[name] = {"value": value, "limit": limit}
        if value is None or not value <= limit:
            correct = False
    also = {k: v for k, v in got.items() if k not in numbers}
    return correct, numbers, also


# ---- the evidence of a run, kept beside its log, and read again ------
FRAME_KEYS = ("lat", "lon", "alt", "trk", "gs", "vs", "asase", "asasn",
              "inconf", "id", "simt")


def save_evidence(path, evidence):
    """One ``.npz``: a run's evidence as ``decide`` takes it, so that a
    limit can be read over many runs, and the control's numbers beside
    them, in one process off the chip (``python3 check.py``)."""
    ev = dict(evidence)
    arrays = {}
    for k, f in enumerate(ev.pop("frames", [])):
        for key in FRAME_KEYS:
            arrays[f"frame{k}_{key}"] = np.asarray(f[key])
    if "states" in ev:
        ev["states"] = [[name, m, got] for (name, m), got
                        in ev["states"].items()]
    arrays["rest"] = np.asarray(json.dumps(ev))
    np.savez_compressed(path, **arrays)


def load_evidence(path):
    with np.load(path) as z:
        ev = json.loads(str(z["rest"]))
        nframes = sum(1 for k in z.files if k.endswith("_simt"))
        if nframes:
            ev["frames"] = [{key: z[f"frame{k}_{key}"] for key in FRAME_KEYS}
                            for k in range(nframes)]
    if "states" in ev:
        ev["states"] = {(name, m): {i: tuple(v) for i, v in got.items()}
                        for name, m, got in ev["states"]}
    return ev


if __name__ == "__main__":
    # python3 benchmark/check.py <configs/x.json> <seed> <evidence.npz>...
    with open(sys.argv[1]) as fh:
        spec_ = json.load(fh)["check"]
    for path_ in sys.argv[3:]:
        ev_ = load_evidence(path_)
        ev_.pop("compares", None)        # every number the spec limits
        for tag, e in (("program", ev_), ("control", control_evidence(
                spec_, ev_, int(sys.argv[2])))):
            ok, numbers_, also_ = decide(spec_, e, int(sys.argv[2]))
            print(path_, tag, f"correct={ok}", json.dumps(
                {k: v["value"] for k, v in numbers_.items()} | also_))
