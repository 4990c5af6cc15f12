"""The comparison that decides ``correct``: what the timed path produced,
as the client received it, against a plain reference.

``decide(spec, evidence, seed)`` returns ``(correct, numbers, also)``:
``numbers`` maps each number compared to ``{"value", "limit"}``, ``also``
holds what else was read and is held to nothing.  A run
is correct when every number the mix compares was read and none is over
its limit.  ``control_evidence`` puts the reference, computed in
bfloat16, in the program's place: the evidence it returns has to come
out as not correct (tests/test_control.py; ``run.py --control 1`` on the
chip).

Kinds of check and references are found by name: ``spec["kind"]`` is a
module ``checks/<kind>.py`` with ``numbers(spec, evidence, seed,
reference)`` and ``control_evidence(spec, evidence, seed, reference)``,
``spec["reference"]`` (``plain`` unless it says) a module
``reference/<name>.py``.  A configuration's ``check`` is its spec; a mix
whose ``probe`` names another kind (``probe.check``) takes that kind's
``sample``, ``limits`` and the rest from the configuration's
``checks.<kind>`` (``spec_of``), and its evidence says so (``check``).
Each kind says in its own file what it compares.
"""
import importlib
import json
import sys

import numpy as np


def spec_of(cfg, kind=None):
    """The spec of check ``kind`` in configuration ``cfg``: its ``check``
    where that is the kind (or none is asked for), else
    ``checks.<kind>``."""
    if kind is None or kind == cfg["check"]["kind"]:
        return cfg["check"]
    return dict(cfg["checks"][kind], kind=kind)


def _modules(spec):
    return (importlib.import_module("checks." + spec["kind"]),
            importlib.import_module("reference."
                                    + spec.get("reference", "plain")))


def control_evidence(spec, evidence, seed):
    kind, ref = _modules(spec)
    return kind.control_evidence(spec, evidence, seed, ref)


def decide(spec, evidence, seed):
    kind, ref = _modules(spec)
    got = kind.numbers(spec, evidence, seed, ref)
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
        cfg_ = json.load(fh)
    for path_ in sys.argv[3:]:
        ev_ = load_evidence(path_)
        spec_ = spec_of(cfg_, ev_.get("check"))
        ev_.pop("compares", None)        # every number the spec limits
        for tag, e in (("program", ev_), ("control", control_evidence(
                spec_, ev_, int(sys.argv[2])))):
            ok, numbers_, also_ = decide(spec_, e, int(sys.argv[2]))
            print(path_, tag, f"correct={ok}", json.dumps(
                {k: v["value"] for k, v in numbers_.items()} | also_))
