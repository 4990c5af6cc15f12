"""From a ``PROFILE DEVICE`` trace to numbers: device busy and idle time,
the time of the chunk programs and of named kernels, the longest device
operations and the idle gaps by where they lie.

A trace is reduced to ``DeviceTrace``: per device, the operation events
(name, start, duration in seconds) and the program ("XLA Modules")
events, read from the profiler's Chrome trace (``*.trace.json.gz``) with
gzip and json: this process never imports JAX.  ``selfcheck.py`` checks
the arithmetic on the small recorded trace in ``testdata/``.
"""
import glob
import gzip
import json
import os

LINES = {"XLA Ops": "ops", "XLA Modules": "modules"}


class DeviceTrace:
    def __init__(self, devices):
        #: {device name: {"ops": [(name, t0, dur)], "modules": [...]}}
        self.devices = devices

    # ---------------------------------------------------------- loading
    @classmethod
    def from_chrome(cls, doc):
        events = doc.get("traceEvents", doc) if isinstance(doc, dict) \
            else doc
        pname, tname = {}, {}
        for ev in events:
            if ev.get("ph") != "M":
                continue
            if ev.get("name") == "process_name":
                pname[ev["pid"]] = ev["args"]["name"]
            elif ev.get("name") == "thread_name":
                tname[(ev["pid"], ev["tid"])] = ev["args"]["name"]
        devices = {}
        for ev in events:
            if ev.get("ph") != "X":
                continue
            proc = pname.get(ev.get("pid"), "")
            if not proc.startswith("/device:") or "CPU" in proc:
                continue
            kind = LINES.get(tname.get((ev["pid"], ev.get("tid")), ""))
            if kind is None:
                continue
            extra = " ".join(str(v) for v in (ev.get("args") or {}).values())
            devices.setdefault(proc, {"ops": [], "modules": []})[kind] \
                .append((ev["name"] + (" | " + extra if extra else ""),
                         ev["ts"] * 1e-6, ev.get("dur", 0) * 1e-6))
        return cls(devices)

    @classmethod
    def from_dir(cls, profile_dir):
        """The newest trace under a ``PROFILE DEVICE`` directory, or
        None when the profiler wrote none."""
        chrome = sorted(glob.glob(os.path.join(
            profile_dir, "plugins", "profile", "*", "*.trace.json.gz")))
        if not chrome:
            return None
        with gzip.open(chrome[-1], "rt") as f:
            return cls.from_chrome(json.load(f))

    # -------------------------------------------------------- reduction
    @staticmethod
    def _leaves(dev):
        """The operations that contain no other: a while, a conditional
        or a call spans the operations inside it, which the trace lists
        too, and its own span covers their idle microseconds."""
        ops = sorted(dev["ops"], key=lambda e: (e[1], -e[2]))
        parent, open_ = set(), []          # open_: (end, index)
        for k, (_, t, d) in enumerate(ops):
            while open_ and open_[-1][0] <= t:
                open_.pop()
            if open_ and t + d <= open_[-1][0] + 1e-12:
                parent.add(open_[-1][1])
            open_.append((t + d, k))
        return [e for k, e in enumerate(ops) if k not in parent]

    @staticmethod
    def _union(intervals):
        """Merged, sorted [start, end) intervals."""
        out = []
        for a, b in sorted(intervals):
            if out and a <= out[-1][1]:
                out[-1][1] = max(out[-1][1], b)
            else:
                out.append([a, b])
        return out

    def summary(self):
        """Averages over the devices that ran anything:
        ``window_s`` first operation start to last operation end,
        ``busy_s`` union of the intervals of the operations that contain
        no others (a while spans its body's idle microseconds too),
        ``module_s`` union of the program intervals."""
        rows = []
        for dev in self.devices.values():
            if not dev["ops"]:
                continue
            busy = self._union((t, t + d) for _, t, d in self._leaves(dev))
            mods = self._union((t, t + d) for _, t, d in dev["modules"])
            rows.append(dict(
                window_s=busy[-1][1] - busy[0][0],
                busy_s=sum(b - a for a, b in busy),
                module_s=sum(b - a for a, b in mods)))
        if not rows:
            return None
        return {k: sum(r[k] for r in rows) / len(rows) for k in rows[0]}

    def time_of(self, patterns):
        """Seconds in the operations (those that contain no other)
        whose name or arguments contain any of ``patterns`` (lower
        case), averaged over the devices, and the number of such
        operations on the busiest device."""
        pats = [p.lower() for p in patterns]
        tot, cnt, ndev = 0.0, 0, 0
        for dev in self.devices.values():
            if not dev["ops"]:
                continue
            ndev += 1
            hit = [d for n, _, d in self._leaves(dev)
                   if any(p in n.lower() for p in pats)]
            tot += sum(hit)
            cnt = max(cnt, len(hit))
        return (tot / ndev, cnt) if ndev else (0.0, 0)

    def program_time(self, pattern):
        """Seconds in the programs whose name contains ``pattern``
        (lower case), averaged over the devices, and how many of them
        the busiest device ran: the chunk programs of a traced span are
        counted in the trace, not taken from what was asked for."""
        tot, cnt, ndev = 0.0, 0, 0
        for dev in self.devices.values():
            if not dev["ops"]:
                continue
            ndev += 1
            hit = [(t, t + d) for n, t, d in dev["modules"]
                   if pattern.lower() in n.lower()]
            tot += sum(b - a for a, b in self._union(hit))
            cnt = max(cnt, len(hit))
        return (tot / ndev, cnt) if ndev else (0.0, 0)

    def breakdown(self, top=10):
        """The contract's ``breakdown``: the device operations that took
        most time, and the idle gaps inside and between programs."""
        per_op, inside, between = {}, [], []
        for dev in self.devices.values():
            if not dev["ops"]:
                continue
            leaves = self._leaves(dev)
            for n, _, d in leaves:
                key = n.split(" | ")[0]
                per_op[key] = per_op.get(key, 0.0) + d
            busy = self._union((t, t + d) for _, t, d in leaves)
            mods = self._union((t, t + d) for _, t, d in dev["modules"])
            k = 0
            for (_, e0), (s1, _) in zip(busy[:-1], busy[1:]):
                while k < len(mods) and mods[k][1] < s1:
                    k += 1
                in_mod = k < len(mods) and mods[k][0] <= e0 \
                    and s1 <= mods[k][1]
                (inside if in_mod else between).append(
                    (s1 - e0, e0 - busy[0][0]))
        ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
        gaps = []
        for label, g in (("between_chunk_programs", between),
                         ("inside_a_chunk_program", inside)):
            if g:
                longest, at = max(g)
                gaps += [[f"{label}:{len(g)}_gaps_total",
                          sum(d for d, _ in g)],
                         [f"{label}:longest_gap_at_{at:.3f}s_of_the_trace",
                          longest]]
        return {"device_ops": [[n, s] for n, s in ops], "idle_gaps": gaps}
