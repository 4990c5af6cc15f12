"""Check kind "interval" (one world, streamed, its frames consumed):
consecutive ACDATA frames a whole number of the mix's chunks apart
(``probe.chunk_sim_s``; a 20-step chunk is one CD interval), as the
client received them while the world free-ran, so it looks between the
1000-step edges that kind "frames" stands on.

A pair of frames is compared over the **ids both hold**: a fleet that
gained or lost aircraft between them still gives a pair, and the share
of ids that only one frame holds is read beside the numbers
(``fleet_changed_share``).  What is compared is what "frames" compares,
with its arithmetic (``frames.numbers_of``): flags and MVP vectors of B
against the reference's detection and resolution on B flown back to its
last detection step, which lies at most one CD interval back
(``cd_interval_s``), whatever the chunk; aircraft that kept their
velocity held to the position update step by step over the steps
between the frames; the others coarsely.
"""
import numpy as np

from . import frames as fr


def pairs(spec, evidence, seed, ref, changed=None):
    """``frames.pairs`` over the ids both frames hold; ``changed``
    collects, for each pair, (ids that one frame alone holds, ids that
    either holds)."""
    frames = fr.frames_of(evidence, ref)
    for kb, (a, b) in enumerate(zip(frames[:-1], frames[1:]), 1):
        in_b = set(b["id"])
        in_a = np.asarray([k for k, acid in enumerate(a["id"])
                           if acid in in_b], dtype=int)
        if changed is not None:
            either = len(a["id"]) + len(b["id"]) - len(in_a)
            changed.append((either - len(in_a), either))
        if len(in_a):
            yield fr.pair_of(a, b, in_a, kb, spec, evidence, seed, ref)


def numbers(spec, evidence, seed, ref):
    changed = []
    back_steps = int(round(float(spec["cd_interval_s"]) / ref.SIMDT))
    out = fr.numbers_of(pairs(spec, evidence, seed, ref, changed), ref,
                        back_steps, "interval_")
    if changed:
        out["interval_pairs"] = float(len(changed))
        out["fleet_changed_share"] = sum(c for c, _ in changed) \
            / max(1, sum(e for _, e in changed))
    return out


def control_evidence(spec, evidence, seed, ref):
    return fr.control_evidence(spec, evidence, seed, ref, pairs=pairs)
