"""Multi-world throughput sweep -> BENCH_WORLDS.json.

Measures aggregate aircraft-steps/s of the world-batched scan
(core/step.run_steps_worlds_edge: one stacked vmapped chunk steps W
scenarios) against the one-piece-per-worker baseline (the same
compiled single-world program dispatched serially — the chip-time a
worker-process fleet sharing one device gets), for W x N in the
small-scenario serving regime the packing layer targets (N in
{100, 500, 2000}).

Every measured row is platform-tagged (the repo's bench convention:
tpu:v5e history and cpu:cpu rows coexist).  On a CPU-only box the
measured ratio is bounded by the core count — a single core is
compute-saturated by ONE world, so batching mostly amortizes per-op
overheads (SURVEY: the 10x regime is idle accelerator lanes).  The
file therefore also carries a CALIBRATED chip projection for the
headline 256 x N=500 fleet, derived from this repo's own TPU-measured
BENCH_DETAIL.json rows: a [256*500 = 128k]-row batched program runs at
the measured N~100k sparse/continental efficiency, while the
one-piece-per-worker fleet pays the measured small-N per-dispatch rate
— the same calibration idiom as BENCH_FULL_INTERVAL.json's projected
spatial rows.

``--quick`` runs the tiny CI matrix (perf-smoke lane).
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def sweep(quick=False):
    import bench

    platform = bench.platform_tag()
    rows = []
    if quick:
        matrix = {100: ((8, 100),), 500: ((8, 50),)}
        reps = 1
    else:
        # W caps bound dense [W,N,N] CD temporaries + wall time on the
        # sweep box; every cap is recorded in the emitted row (no
        # silent coverage cuts)
        matrix = {
            100: ((4, 200), (16, 200), (64, 200), (256, 100)),
            500: ((4, 100), (16, 60), (64, 60), (256, 40)),
            2000: ((4, 40), (16, 30), (32, 30)),
        }
        reps = 1
    w_cap = {2000: 32}
    for n, wspecs in matrix.items():
        baseline = None
        for w, nsteps in wspecs:
            row, base = bench.run_worlds(n, w, nsteps=nsteps, reps=reps)
            row["platform"] = base["platform"] = platform
            if n in w_cap:
                row["w_cap"] = w_cap[n]
                row["w_cap_reason"] = ("dense [W,N,N] CD temporaries: "
                                       f"{w_cap[n]}x{n}^2 f32 bounds "
                                       "sweep-box memory")
            if baseline is None:
                baseline = base
                rows.append(base)
            rows.append(row)
            print(json.dumps(row), flush=True)
    return rows, platform


def chip_projection():
    """Calibrated accelerator projection for the 256 x N=500 headline,
    from this repo's own TPU-measured BENCH_DETAIL.json rows (same
    idiom as BENCH_FULL_INTERVAL.json's projected spatial column) —
    conservative on BOTH ends:

    * one-piece-per-worker baseline: each dispatch runs a SMALL-N
      program whose per-step wall time is fixed-cost (latency) bound on
      the chip; the measured dense N=1000/regional ac-steps/s is an
      UPPER bound on an N=500 dispatch (same per-step latency, half
      the rows per step).
    * world-batched: one [256 x 500 = 128k]-row program; the measured
      sparse N~100k/global row OVERSTATES its cost — 256 independent
      500-aircraft worlds have ZERO cross-world pairs (the vmapped CD
      is within-world by construction, ~32M reachable pairs/interval
      total), less CD work than even the lowest-density measured 100k
      single fleet.
    """
    try:
        detail = json.load(open(os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "BENCH_DETAIL.json")))
    except OSError:
        return None
    byrow = {(r["n"], r["backend"], r["geometry"]):
             r["ac_steps_per_s"] for r in detail if not r.get("failed")}
    base = byrow.get((1000, "dense", "regional"))
    batched = byrow.get((100000, "sparse", "global"))
    if not base or not batched:
        return None
    return {
        "n": 500, "worlds": 256, "projected": True,
        "platform": "tpu:v5e (calibrated from BENCH_DETAIL.json)",
        "baseline_ac_steps_per_s": base,
        "baseline_basis": "measured dense N=1000 regional row — an "
                          "UPPER bound on an N=500 per-dispatch rate "
                          "(same fixed per-step latency, half the "
                          "rows)",
        "batched_ac_steps_per_s": batched,
        "batched_basis": "measured sparse N=100k global row — "
                         "OVERSTATES the 128k-row batch's cost (256 "
                         "independent worlds carry zero cross-world "
                         "pairs, so less CD work than any measured "
                         "100k single fleet)",
        "speedup": round(batched / base, 1),
    }


def main():
    import bench
    quick = "--quick" in sys.argv
    path = bench.pop_out_flag(sys.argv, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "BENCH_WORLDS.json"))
    reproject = "--reproject" in sys.argv
    if reproject:
        # refresh the calibrated projection/headline over the existing
        # measured rows without re-running the sweep
        old = json.load(open(path))
        rows = old["rows"]
        platform = next((r["platform"] for r in rows
                         if "platform" in r), "cpu:cpu")
    else:
        rows, platform = sweep(quick=quick)
    # measured headline: the largest N=500 batched row vs its baseline
    measured = None
    n500 = [r for r in rows if r["n"] == 500 and r.get("worlds", 1) > 1]
    if n500:
        best = max(n500, key=lambda r: r["worlds"])
        measured = {
            "platform": platform, "n": 500, "worlds": best["worlds"],
            "speedup": best.get("speedup"),
            "note": ("single-core CPU boxes are compute-saturated by "
                     "one world; the >=10x regime is idle accelerator "
                     "lanes — see projected_chip_headline")
            if platform.startswith("cpu") else None,
        }
    # shared tagging + writing boilerplate lives in bench.py now; a
    # reprojection re-derives headlines over rows that were already
    # recorded, so it must not double-append to BENCH_HISTORY (keeps
    # --reproject round-trips byte-identical on the JSON too)
    bench.write_bench_json(path, rows, history=not reproject,
                           projected_chip_headline=chip_projection(),
                           measured_headline=measured)


if __name__ == "__main__":
    main()
