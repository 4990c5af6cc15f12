"""BlueSky's trafgen sweep as its source runs it, as stack commands: the
standing fleet of ``circle_fleet`` (the disc of ``plugins/trafgen.py``
filled to the sweep's top density, so that a window opens on it and not
hours before it), then the plugin's own edge sources and ``AREA``.

``PLUGINS LOAD TRAFGEN`` and ``PLUGINS LOAD AREA`` come first, so that
the ``SEED`` line ``circle_fleet`` derives from ``--seed`` also seeds the
sources' Poisson draws (as BlueSky's ``SEED`` seeds the generator its
trafgen draws from).  ``TRAFGEN CIRCLE`` sets the disc; every
``bearing_step_deg`` degrees a ``SEGM<brg>`` source with an equal share
of ``flow_per_s`` (aircraft a simulated second in all), the altitude and
CAS windows of ``MCRE`` and every other segment as a destination; ``AREA
SPAWN`` deletes what leaves the disc.  The world is held while all this
is read, so no source ticks before the mix starts it.

``fill_centre`` and ``fill_radius_nm`` (the circle's own unless given)
say where the standing fleet stands: a rehearsal crowds its toy fleet
into a small disc across the circle's northern edge, under ``SEGM0``,
so that a run of a minute has spawns among it and leavers.
"""
from . import circle_fleet


def commands(params, seed, n):
    # the flow is a function of --seed only where the SEED line reaches
    # the sources' generator; a tree whose TRAFGEN draws from a fixed
    # one spawns the same aircraft whatever the seed, and cannot run
    # this configuration: say so before anything is built
    from bluesky_tpu.plugins import trafgen
    if not hasattr(trafgen.TrafGen, "seed"):
        raise SystemExit("benchmark: this tree's TRAFGEN cannot be seeded "
                         "(no TrafGen.seed): the configuration's flow is "
                         "Poisson from --seed")
    clat, clon = params["centre"]
    segments = [f"SEGM{b}" for b in
                range(0, 360, int(params["bearing_step_deg"]))]
    per_hour = 3600.0 * float(params["flow_per_s"]) / len(segments)
    alt, spd = params["spawn_alt_ft"], params["spawn_cas_kts"]
    cmds = ["PLUGINS LOAD TRAFGEN", "PLUGINS LOAD AREA",
            f"TRAFGEN CIRCLE {clat} {clon} {params['radius_nm']}"]
    cmds += circle_fleet.commands(
        dict(params, centre=params.get("fill_centre", params["centre"]),
             radius_nm=params.get("fill_radius_nm", params["radius_nm"])),
        seed, n)
    for name in segments:
        cmds += [f"TRAFGEN SRC {name} FLOW {per_hour:g}",
                 f"TRAFGEN SRC {name} ALT {alt[0]} {alt[1]}",
                 f"TRAFGEN SRC {name} SPD {spd[0]} {spd[1]}",
                 f"TRAFGEN SRC {name} DEST "
                 + " ".join(s for s in segments if s != name)]
    return cmds + ["AREA SPAWN"]
