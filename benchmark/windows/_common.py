"""What the window kinds share: the set-up's timeline, the generator a
configuration names, the worker's count of chunks, and the probe that
keeps frames after a window."""
import importlib
import re
import sys
import time


def stage(args, what):
    """The set-up's timeline, on standard error: where its seconds go."""
    print(f"setup: {what} at {time.perf_counter() - args.t_process:.1f} s",
          file=sys.stderr, flush=True)


def generator(name):
    return importlib.import_module(f"generators.{name}")


def chunk_count(mix, dumps):
    """The chunks the workers had retired when each wrote its METRICS
    DUMP (``dumps``: ``{worker id: text}``), summed, by the histogram
    the mix names (``chunk_counter``); None where no worker has it."""
    found = [re.search(mix["chunk_counter"] + r": n=(\d+)", text)
             for text in dumps.values()]
    return sum(int(f[1]) for f in found if f) if any(found) else None


def probe_frames(sv, probe):
    """After the window: hold, then drive the mix's own programs a
    little further with an ACDATA consumer attached, and keep the
    frames.  Returns them with distinct simulated times, in order."""
    s, client = sv.s, sv.client
    client.stack("HOLD")
    _, held = s.wait_state(lambda r: r["state"] == 1, 300.0, "HOLD")
    client.subscribe(b"ACDATA")
    n0 = len(s.acdata_t)
    s.wait(lambda: len(s.acdata_t) >= n0 + 2, 120.0,
           "frames of the held state")
    s.keep_frames = [s.acdata]
    t0 = s.acdata["simt"]
    client.stack("; ".join(probe["commands"]))
    s.wait(lambda: s.keep_frames[-1]["simt"] >= t0
           + float(probe["collect_sim_s"]) - 0.25, 900.0,
           "the probe's frames")
    client.stack("HOLD")
    frames, s.keep_frames = s.keep_frames, None
    return frames
