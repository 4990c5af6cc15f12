"""Monte-Carlo ASAS-WALL pieces: BlueSky's ``SYN WALL`` (one ownship
flying east into a wall of twenty flying west), then a selected heading
and speed for every aircraft, perturbed from the seed, as plain stack
commands.  ``SYN WALL`` creates the fleet in one write; ``CRE`` lines
would flush the device state once per aircraft (one write program each
since PR 26, a few milliseconds on the chip; a quarter of a second
before it: CHANGES.md, PR 26).

``pieces(params, seed, count, tag, ids)`` returns ``count`` pieces, each
a dict with the piece's ``name``, its ``aircraft`` (as created: ``lat``,
``lon``, ``hdg``, ``alt_m``, ``cas_ms``; as selected: ``sel_hdg``,
``sel_cas_ms``), the times of its ``marks`` and the scenario lines
(``scentime`` [s], ``scencmd``).  The program sees only the lines; the
plain reference steps the aircraft.  ``ids`` are the callsigns ``SYN
WALL`` gives under the piece's ``SEED`` line, which the harness reads
from the worker once (``discover``).  No two pieces are alike: the
perturbations are drawn from (seed, stream, index) and the name is in
the lines.  At each mark the piece echoes its name and the mark, then
``POS`` of the aircraft ``echo_aircraft`` lists for that mark (0 is the
ownship; a POS is one gather and one transfer since PR 26 and cost the
worker 10.5 to 11.7 ms before it, so not all 21: CHANGES.md, PR 26); the
last mark ends the piece with ``HOLD``.
"""
import numpy as np

KTS, FT, NM = 0.514444, 0.3048, 1852.0
MPERDEG = 111319.0                 # synthetic.py's metres per degree


def discover(params):
    """Stack lines after which an ACDATA frame lists the ids."""
    return [f"SEED {int(params['id_seed'])}", "SYN WALL"]


def pieces(params, seed, count, tag, ids):
    nwall, dist = 20, 0.6          # SYN WALL
    sep = 5.0 * NM / MPERDEG * 1.1
    if len(ids) != nwall + 1:
        raise ValueError(f"SYN WALL made {len(ids)} aircraft, not 21")
    own = [i for i in ids if i == "OWNSHIP"]
    order = own + [i for i in ids if i != "OWNSHIP"]
    marks = [float(t) for t in params["marks_s"]]
    out = []
    for k in range(count):
        rng = np.random.default_rng([int(seed), int(params["stream"]), k])
        name = f"{tag}{k:03d}"
        dh = rng.uniform(-params["hdg_noise_deg"], params["hdg_noise_deg"],
                         nwall + 1)
        ds = rng.uniform(-params["spd_noise_kts"], params["spd_noise_kts"],
                         nwall + 1)
        ac = [dict(id=order[0], lat=0.0, lon=-dist, hdg=90.0,
                   alt_m=20000 * FT, cas_ms=200.0,
                   sel_hdg=round(90.0 + dh[0], 2),
                   sel_cas_kts=round(params["own_cas_kts"] + ds[0], 1))]
        for w in range(nwall):
            ac.append(dict(id=order[w + 1], lat=(w - 10) * sep, lon=dist,
                           hdg=270.0, alt_m=20000 * FT, cas_ms=200.0 * KTS,
                           sel_hdg=round(270.0 + dh[w + 1], 2),
                           sel_cas_kts=round(params["wall_cas_kts"]
                                             + ds[w + 1], 1)))
        cmds = [(0.0, f"SCEN {name}"),
                (0.0, f"SEED {int(params['id_seed'])}"), (0.0, "ASAS ON")]
        cmds += [(0.0, c) for c in params["setup_commands"]]
        cmds += [(0.0, "SYN WALL")]
        for a in ac:
            cmds += [(0.0, f"HDG {a['id']} {a['sel_hdg']}"),
                     (0.0, f"SPD {a['id']} {a['sel_cas_kts']}")]
        cmds += [(0.0, "FF")]
        for j, a in enumerate(ac):
            a["echoed"] = [j in at for at in params["echo_aircraft"]]
        for m, t in enumerate(marks):
            cmds += [(t, f"ECHO {name} MARK{m}")]
            cmds += [(t, f"POS {a['id']}") for a in ac if a["echoed"][m]]
        cmds += [(marks[-1], "HOLD")]
        for a in ac:
            a["sel_cas_ms"] = a.pop("sel_cas_kts") * KTS
        out.append(dict(name=name, aircraft=ac, marks_s=marks,
                        scentime=[t for t, _ in cmds],
                        scencmd=[c for _, c in cmds]))
    return out
