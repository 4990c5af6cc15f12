"""Check kind "pieces" (a batch of small worlds): a seeded sample of the
pieces the window finished, each stepped by the reference from the
piece's own lines to the time of its last commands, against the
positions the piece echoed there; and the journal's word that no piece
completed twice or crashed."""
import numpy as np

from .frames import sample


def step_pieces(pieces, q, nmarks, ref):
    """The pieces stepped by the reference, each alone in a world of its
    own (one array, no pair across worlds); returns, for each of the
    first ``nmarks`` marks, the positions of all their aircraft.  A
    mark's commands run at the first step at or after its time, on the
    float32 clock of the simulation."""
    ac = [a for p in pieces for a in p["aircraft"]]
    world = [k for k, p in enumerate(pieces) for _ in p["aircraft"]]
    col = lambda k: [a[k] for a in ac]          # noqa: E731
    st = ref.new_fleet(col("lat"), col("lon"), col("hdg"), col("alt_m"),
                       col("cas_ms"), col("sel_hdg"), col("sel_cas_ms"),
                       world, q)
    at = []
    for t in pieces[0]["marks_s"][:nmarks]:
        while st["simt"] < np.float32(t):
            ref.step(st, q)
        at.append((st["lat"].copy(), st["lon"].copy()))
    return at


def numbers(spec, evidence, seed, ref):
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
    pick = [pieces[k] for k in sample(len(pieces), int(spec["sample"]),
                                      seed)]
    nmarks = int(spec["follow_marks"])
    gaps, worst = [[] for _ in range(nmarks)], {}
    missing = sum(1 for p in pieces for m in range(len(p["marks_s"]))
                  for a in p["aircraft"] if a["echoed"][m]
                  and a["id"] not in states.get((p["name"], m), {}))
    for m, (lat, lon) in enumerate(step_pieces(pick, ref.Precision(),
                                               nmarks, ref)):
        i = 0
        for p in pick:
            got = states.get((p["name"], m), {})
            for a in p["aircraft"]:
                if a["echoed"][m] and a["id"] in got:
                    la, lo = got[a["id"]]
                    dn = np.radians(la - float(lat[i])) * ref.REARTH
                    de = np.radians(lo - float(lon[i])) * ref.REARTH \
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


def control_evidence(spec, evidence, seed, ref):
    """The same evidence with the reference in bfloat16 standing where
    the program stood: the positions at every followed mark of the
    sampled pieces stepped in bfloat16."""
    q = ref.Precision("bfloat16")
    ev = dict(evidence)
    states = dict(evidence["states"])
    pieces = evidence["pieces"]
    pick = [pieces[k] for k in sample(len(pieces), int(spec["sample"]),
                                      seed)]
    for m, (lat, lon) in enumerate(step_pieces(
            pick, q, int(spec["follow_marks"]), ref)):
        i = 0
        for p in pick:
            states[(p["name"], m)] = {
                a["id"]: (float(lat[i + k]), float(lon[i + k]))
                for k, a in enumerate(p["aircraft"])}
            i += len(p["aircraft"])
    ev["states"] = states
    return ev
