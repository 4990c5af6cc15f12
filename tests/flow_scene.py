"""A toy world whose fleet turns over, shared by tests/test_flow.py and
by the script that recorded its golden file on the parent tree: TRAFGEN
sources on a 6 nm circle, AREA deleting what leaves it."""
import numpy as np

from bluesky_tpu.simulation.sim import Simulation

CIRCLE = (52.6, 5.4, 6.0)
FIELDS = ("lat", "lon", "alt", "hdg", "tas", "cas", "selspd", "selalt")


def do(sim, *lines):
    for line in lines:
        sim.stack.stack(line)
    sim.stack.process()
    out = "\n".join(sim.scr.echobuf)
    sim.scr.echobuf.clear()
    return out


def flow_world(nmax=256, standing=40, flow_per_h=600, area=True,
               pipeline=True, backend="SPARSE"):
    """Held: ``standing`` aircraft about the circle's centre, twelve
    SEGM sources of ``flow_per_h`` each with every other segment a
    destination, AREA on the circle."""
    sim = Simulation(nmax=nmax)
    sim.pipeline_enabled = pipeline
    out = do(sim, "HOLD", "SEED 7", f"CDMETHOD {backend}", "RESO MVP",
             "PAN 52.6 5.4", "ZOOM 10", f"MCRE {standing}",
             "PLUGINS LOAD TRAFGEN")
    out += do(sim, "TRAFGEN CIRCLE %g %g %g" % CIRCLE)
    segs = [f"SEGM{b}" for b in range(0, 360, 30)]
    for s in segs:
        out += do(sim, f"TRAFGEN SRC {s} DEST "
                  + " ".join(d for d in segs if d != s),
                  f"TRAFGEN SRC {s} FLOW {flow_per_h}")
    if area:
        out += do(sim, "PLUGINS LOAD AREA", "AREA SPAWN")
    assert "nknown" not in out and "rror" not in out, out
    return sim


def trafgen_generator(sim):
    """The TrafGen instance behind a loaded plugin."""
    return sim.stack.cmddict["TRAFGEN"][2].__self__


def rows_of(sim, ids):
    st = sim.traf.state
    cols = [np.asarray(getattr(st.ac, f)) for f in FIELDS]
    extra = [np.asarray(st.ac.swlnav), np.asarray(st.ac.swvnav),
             np.asarray(st.route.nwp), np.asarray(st.actwp.lat),
             np.asarray(st.actwp.lon), np.asarray(st.route.wplat)[:, 0]]
    return {i: [float(c[sim.traf.id2idx(i)]) for c in cols + extra]
            for i in ids}


def trafgen_record(ticks=200):
    """``ticks`` TRAFGEN ticks (0.1 s each) of a world with sources
    alone: for every tick that created aircraft, its time, their
    callsigns and their rows as the tick left them."""
    sim = flow_world(area=False)
    trafgen_generator(sim).rng = np.random.default_rng(12345)
    sim.op()
    sim.fastforward()
    record, known = [], set(i for i in sim.traf.ids if i)
    for k in range(1, ticks + 1):
        sim.run(until_simt=0.1 * k)
        # the recorded tree gave guidance by stacked lines, read by the
        # pass of the stack that precedes the next chunk: no time passes
        sim.stack.process()
        new = sorted(set(i for i in sim.traf.ids if i) - known)
        if new:
            known |= set(new)
            record.append([round(float(sim.simt), 4), new,
                           rows_of(sim, new)])
    return record
