"""Measure the headline's sensitivity to the scan-chunk length, with
the async chunk pipeline ON and OFF (VERDICT r4 #6 + ISSUE 4).

Runs the bench.run_chunked protocol — the production Simulation.step
cost model: per-chunk host re-sort, per-edge telemetry consumption —
at chunk = 20 / 100 / 400 / 1000 steps over the same total step count,
and emits one JSON row per (chunk, pipeline) cell including the
host-edge overhead breakdown (dispatch gap + telemetry-pull time per
chunk).  20 is the production interactive default, 1000 the FF/BATCH
headline protocol; the pipeline's job is to close the gap between
them.

Every row carries a ``gap_vs_ff`` column (ISSUE 15): its x_realtime
divided by the best x_realtime of the LARGEST-chunk row in the same
(platform, backend, n, pipeline) group — 1.0 is "no interactive-chunk
penalty vs the FF/BATCH headline", the tpu:v5e 20-step host-re-sort
row sits at ~0.30.

Rows land in output/chunk_sweep.json AND are merged into the repo-root
BENCH_CHUNK_SWEEP.json: rows are replaced per (platform, backend, n)
triple, everything else (e.g. the historical TPU v5e sweep, the CPU
dense sweep) is kept — and the gap_vs_ff column is (re)derived across
the merged set so kept rows get it too.

Usage: python scripts/chunk_sweep.py [N] [--pipeline on|off|both]
       [--total-steps S] [--backend sparse|dense|tiled|pallas]
"""
import json
import os
import sys

sys.path.insert(0, ".")

import bench  # noqa: E402


def main(n_ac=100_000, pipeline="both", total_steps=1000,
         backend=None):
    modes = {"on": [True], "off": [False],
             "both": [False, True]}[pipeline]
    plat = bench.platform_tag()
    rows = []
    for nsteps in (20, 100, 400, 1000):
        for pipe in modes:
            r = bench.run_chunked(n_ac, backend=backend,
                                  geometry="continental", chunk=nsteps,
                                  total_steps=max(total_steps, nsteps),
                                  pipeline=pipe, reps=3)
            r["platform"] = plat
            rows.append(r)
            print(json.dumps(r), flush=True)
    add_gap_vs_ff(rows)
    # fresh checkout: output/ may not exist yet — a multi-minute run
    # must not crash at the final dump
    os.makedirs("output", exist_ok=True)
    with open("output/chunk_sweep.json", "w") as f:
        json.dump(rows, f, indent=1)
    merge_bench_file(rows, plat)
    return rows


def _gap_group(r):
    return (r.get("platform", "tpu:v5e"), r.get("backend"),
            r.get("n"), r.get("pipeline"))


def add_gap_vs_ff(rows):
    """Annotate rows with ``gap_vs_ff``: x_realtime over the best
    x_realtime among the group's largest-chunk rows.  Grouping is
    (platform, backend, n, pipeline) — deliberately NOT protocol, so
    a model-projected row normalises against the measured headline."""
    groups = {}
    for r in rows:
        groups.setdefault(_gap_group(r), []).append(r)
    for g in groups.values():
        chunks = [r["nsteps_chunk"] for r in g
                  if isinstance(r.get("nsteps_chunk"), (int, float))]
        if not chunks:
            continue
        cmax = max(chunks)
        ff = max((r.get("x_realtime") or 0.0) for r in g
                 if r.get("nsteps_chunk") == cmax)
        if not ff:
            continue
        for r in g:
            if isinstance(r.get("x_realtime"), (int, float)):
                r["gap_vs_ff"] = round(r["x_realtime"] / ff, 3)
    return rows


def merge_bench_file(rows, plat, path="BENCH_CHUNK_SWEEP.json"):
    """Replace matching (platform, backend, n) rows in
    BENCH_CHUNK_SWEEP.json, keep the rest (the historical TPU sweep
    and the CPU dense sweep stay on record when re-running one config).
    The gap_vs_ff column is re-derived over the merged set so kept
    rows gain it retroactively.  Writes through the shared bench
    writer; only the NEW rows go to BENCH_HISTORY (the kept rows were
    recorded by the run that measured them)."""
    old = []
    if os.path.isfile(path):
        try:
            with open(path) as f:
                old = json.load(f)
        except (OSError, ValueError):
            old = []
    if isinstance(old, dict):               # shared writer format
        old = old.get("rows", [])
    new_keys = {(r.get("platform", plat), r.get("backend"), r.get("n"))
                for r in rows}
    kept = [r for r in old
            if (r.get("platform", "tpu:v5e"), r.get("backend"),
                r.get("n")) not in new_keys]
    merged = add_gap_vs_ff(kept + rows)
    bench.write_bench_json(path, merged, history=False)
    bench.append_history(os.path.splitext(os.path.basename(path))[0],
                         rows, tag=plat)


if __name__ == "__main__":
    # positional parse: consume each flag's value by INDEX, never by
    # textual equality (``chunk_sweep.py 400 --total-steps 400`` must
    # keep N=400)
    argv = sys.argv[1:]
    pipeline = "both"
    total = 1000
    backend = None
    if "--pipeline" in argv:
        i = argv.index("--pipeline")
        pipeline = argv[i + 1].lower()
        del argv[i:i + 2]
    if "--total-steps" in argv:
        i = argv.index("--total-steps")
        total = int(argv[i + 1])
        del argv[i:i + 2]
    if "--backend" in argv:
        i = argv.index("--backend")
        backend = argv[i + 1].lower()
        del argv[i:i + 2]
    args = [a for a in argv if not a.startswith("--")]
    main(int(args[0]) if args else 100_000, pipeline=pipeline,
         total_steps=total, backend=backend)
