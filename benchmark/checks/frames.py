"""Check kind "frames" (one world, streamed): two ACDATA frames one chunk
of the mix's own programs apart (1000 steps in fast-forward), as the
client received them.  The flags and MVP resolution vectors of frame B
come from a detection up to one interval before B, on a state no client
sees: the reference detects and resolves, for a seeded sample of
ownships against all aircraft, on B flown back to that step on its own
velocity, an approximation, with the wider limits that earns.  Aircraft
that kept their velocity over the chunk flew straight and are held to
BlueSky's position update step by step; the others, which the autopilot
and MVP turned, are held coarsely to the mean of the two frames'
velocities.

Steps are counted, never seconds: the reference tells from the frames'
own ``simt`` which clock wrote them (``reference.clock_of``: today's
float32 sum of 0.05 s steps, or a step count times 0.05 s rounded once)
and says how many steps that clock had made at a frame (``steps_at``).

``numbers(spec, evidence, seed, reference)`` and ``control_evidence``
take the reference as the module the spec names (``check.py``).
"""
from fractions import Fraction

import numpy as np


def sample(n, k, seed):
    rng = np.random.default_rng([int(seed), 77])
    return np.sort(rng.choice(n, size=min(k, n), replace=False))


def frame_arrays(frame):
    return {k: np.asarray(frame[k], np.float32)
            for k in ("lat", "lon", "alt", "trk", "gs", "vs",
                      "asase", "asasn")} | {
        "inconf": np.asarray(frame["inconf"], bool),
        "simt": float(frame["simt"]), "id": list(frame["id"])}


def clock_of(evidence, ref):
    """The clock that wrote the evidence's frames."""
    return ref.clock_of(float(f["simt"]) for f in evidence["frames"])


def frames_of(evidence, ref):
    """The evidence's frames as arrays, each with the clock that wrote
    them all (``clock``)."""
    clock = clock_of(evidence, ref)
    return [dict(frame_arrays(f), clock=clock) for f in evidence["frames"]]


def steps_between(a, b, ref):
    """The steps the clock made from frame A to frame B."""
    return ref.steps_at(b["simt"], b["clock"]) \
        - ref.steps_at(a["simt"], a["clock"])


def steps_since_detection(simt, nmax, ref, clock):
    """The steps since the last detection at a chunk edge whose frame
    carries ``simt``.  The program detects at the first step whose clock
    has reached the next whole second.  Clock "sum", today's: a float32
    sum of 0.05 s steps, which runs 0.02% slow below 1024 s and 0.1% fast
    above it, so the instant drifts through the chunk: replays the clock
    back from the edge.  Clock "count": the first step whose exact time,
    its count times 0.05 s, has reached the whole second."""
    if clock == "count":
        n, dt = ref.steps_at(simt, clock), \
            Fraction(ref.SIMDT).limit_denominator(1 << 20)
        whole = (n - 1) * dt // 1          # the last step's whole second
        return min(nmax, n - int(-(-whole // dt)))
    f32, dt = np.float32, np.float32(ref.SIMDT)
    inc = float(f32(f32(simt) + dt) - f32(simt))      # a step of the clock
    last = float(f32(simt)) - inc                     # the last step's start
    return min(nmax, int((last - np.floor(last)) / inc) + 1)


def pair_of(a, b, in_a, kb, spec, evidence, seed, ref):
    """One pair of frames as ``pairs`` yields it, its ownships sampled
    from the places ``in_a`` of frame A, whose ids frame B holds too."""
    chunk_s = float(evidence["chunk_sim_s"])
    pos_b = {acid: k for k, acid in enumerate(b["id"])}
    own = in_a[sample(len(in_a), int(spec["sample"]), seed)]
    ob = np.asarray([pos_b[a["id"][i]] for i in own])
    # whole chunks between the frames, by the mix's chunk length and
    # the steps the frames' clock made: its seconds drift
    chunk = int(round(chunk_s / ref.SIMDT))
    nst = chunk * max(1, int(round(steps_between(a, b, ref) / chunk)))
    flagged = np.flatnonzero(b["inconf"])
    return a, b, own, ob, nst, flagged[sample(
        len(flagged), int(spec["conflict_sample"]), seed)], kb


def pairs(spec, evidence, seed, ref):
    """Consecutive frames with the same fleet: (A, B, the sampled
    ownships' places in A and in B, the steps between the frames, the
    places in B of a second sample, of the ownships B flags as in
    conflict: two in a hundred aircraft are, too few of a plain sample
    to hold the resolution to anything; and B's place among the
    frames)."""
    frames = frames_of(evidence, ref)
    for kb, (a, b) in enumerate(zip(frames[:-1], frames[1:]), 1):
        if len(b["id"]) == len(a["id"]):
            yield pair_of(a, b, np.arange(len(a["id"])), kb, spec, evidence,
                          seed, ref)


def flown_back(b, steps, ref):
    """Frame B flown back ``steps`` steps on its own velocity."""
    back_s = np.float32(ref.SIMDT * steps)
    back = dict(b)
    hr = np.radians(b["trk"])
    back["lat"] = b["lat"] - np.degrees(
        back_s * b["gs"] * np.cos(hr) / np.float32(ref.REARTH))
    back["lon"] = b["lon"] - np.degrees(
        back_s * b["gs"] * np.sin(hr)
        / np.cos(np.radians(b["lat"])) / np.float32(ref.REARTH))
    back["alt"] = b["alt"] - back_s * b["vs"]
    return back


def numbers_of(pairs_, ref, back_steps, prefix):
    """The numbers of a kind of check that compares pairs of frames,
    over the pairs ``pairs_`` yields; ``back_steps``: the most steps
    before a frame that its last detection can lie; ``prefix``: the
    kind's word in the names of its numbers."""
    q = ref.Precision()
    acc = {k: [] for k in ("n", "flag_miss", "reso_gap", "conf_seen",
                           "conf_sampled",
                           "steady_n", "steady_gap", "steady_flag_miss",
                           "turned_gap", "conf_n", "conf_steady")}
    for a, b, own, ob, nst, cob, _ in pairs_:
        # flags and vectors of B: detected some steps before B
        back = flown_back(b, steps_since_detection(
            b["simt"], back_steps, ref, b["clock"]), ref)
        inconf, _, _ = ref.interval_of_sample(ob, back, q)
        miss = inconf != b["inconf"][ob]
        acc["n"].append(len(ob))
        acc["flag_miss"].append(int(miss.sum()))
        if len(cob):
            both, ase, asn = ref.interval_of_sample(cob, back, q)
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
            acc["steady_gap"].append(ref.dead_reckon(
                a, b, own[steady], ob[steady], nst, q))
        if (~steady).any():
            acc["turned_gap"].append(ref.dead_reckon(
                a, b, own[~steady], ob[~steady], nst, q))
        conf = a["inconf"][own] & b["inconf"][ob]
        acc["conf_n"].append(int(conf.sum()))
        acc["conf_steady"].append(int((conf & steady).sum()))
    out = {}
    n = sum(acc["n"])
    if not n:
        return out
    out[prefix + "flag_mismatch_share"] = sum(acc["flag_miss"]) / n
    out[prefix + "unsteady_share"] = 1.0 - sum(acc["steady_n"]) / n
    dv = np.concatenate(acc["reso_gap"] or [np.zeros(0)])
    if len(dv):
        # the ownships in conflict by both: the gap [m/s] between the
        # resolution vector B carries and the reference's MVP
        out[prefix + "reso_gap_p50_ms"] = float(np.percentile(dv, 50))
        out[prefix + "reso_gap_p90_ms"] = float(np.percentile(dv, 90))
        out[prefix + "reso_compared"] = float(len(dv))
        # of the ownships B flags, the share the reference flags too
        out[prefix + "flagged_confirmed_share"] = sum(acc["conf_seen"]) \
            / sum(acc["conf_sampled"])
    if acc["steady_gap"]:
        out[prefix + "position_gap_p99_m"] = float(np.percentile(
            np.concatenate(acc["steady_gap"]), 99))
        out[prefix + "steady_flag_mismatch_share"] = \
            sum(acc["steady_flag_miss"]) / sum(acc["steady_n"])
    if acc["turned_gap"]:
        g = np.concatenate(acc["turned_gap"])
        out[prefix + "turned_position_gap_p90_m"] = float(np.percentile(g, 90))
    if sum(acc["conf_n"]):
        out[prefix + "conflict_steady_share"] = sum(acc["conf_steady"]) \
            / sum(acc["conf_n"])
    return out


def numbers(spec, evidence, seed, ref):
    chunk_steps = int(round(float(evidence["chunk_sim_s"]) / ref.SIMDT))
    return numbers_of(pairs(spec, evidence, seed, ref), ref, chunk_steps,
                      "chunk_")


def control_evidence(spec, evidence, seed, ref, pairs=pairs):
    """The same evidence with the reference in bfloat16 standing where
    the program stood: what frame B carries for the sampled ownships
    recomputed in bfloat16 (flags and resolution vectors from B's own
    state, positions flown on from frame A)."""
    q = ref.Precision("bfloat16")
    ev = dict(evidence)
    frames = [dict(f) for f in evidence["frames"]]
    for a, b, own, ob, nst, _, kb in pairs(spec, evidence, seed, ref):
        out = frames[kb]
        # flags and vectors: the sampled ownships and every one B
        # flags, so that whichever the comparison samples is bfloat16
        every = np.union1d(ob, np.flatnonzero(b["inconf"]))
        inconf, ase, asn = ref.interval_of_sample(every, b, q)
        lat, lon = ref.fly(a, b, own, ob, nst, q)
        for key, val, at in (("inconf", inconf, every),
                             ("asase", ase, every),
                             ("asasn", asn, every),
                             ("lat", lat, ob), ("lon", lon, ob)):
            out[key] = np.array(b[key])
            out[key][at] = val
    ev["frames"] = frames
    return ev
