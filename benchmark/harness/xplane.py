"""From a profiler trace (``*.xplane.pb``) to the numbers the per-layer
readers use. Two steps, so that the arithmetic can be checked without a
trace reader:

1. :func:`read_planes` reads the device planes' events with
   ``jax.profiler.ProfileData`` (the only place under ``benchmark/`` that
   imports JAX; run as a helper process with ``JAX_PLATFORMS=cpu`` once the
   node child is gone, so it never touches the chip):
   ``python benchmark/harness/xplane.py <trace.xplane.pb> <out.json>``.
2. :func:`summarize` is plain Python over those events: the union of busy
   intervals, time per XLA module (the jitted program) and self time per
   op. The program's spans and scopes, and the idle gaps by what the
   dispatchers were doing, are ``xplane_spans.py``'s.

On a TPU the device plane (``/device:TPU:<n>``) carries a line of module
events (``XLA Modules``) and a line of op events (``XLA Ops``); op events
nest (a ``while`` spans its body's ops), so busy time is a union and an
op's own time is its duration less its children's.
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys

MODULE_LINES = ("XLA Modules",)
OP_LINES = ("XLA Ops",)
NAME_CHARS = 100


def find_xplane(log_dir: str) -> str | None:
    files = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(files, key=os.path.getmtime) if files else None


def read_planes(path: str) -> list:
    """[{name, lines: [{name, events: [[name, start_ns, dur_ns], ...]}]}]
    for every device plane of the trace."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:"):
            continue
        lines = []
        for line in plane.lines:
            # (op names are whole HLO instructions: the head tells them apart)
            evs = [[ev.name[:NAME_CHARS], float(ev.start_ns),
                    float(ev.duration_ns)] for ev in line.events]
            lines.append({"name": line.name, "events": evs})
        out.append({"name": plane.name, "lines": lines})
    return out


def _union(intervals: list) -> list:
    """Merged [start, end] intervals, ascending."""
    merged: list = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1][1] = b
        else:
            merged.append([a, b])
    return merged


def _self_times(events: list) -> dict:
    """name -> own seconds, for events that nest on one line."""
    own: dict = {}
    stack: list = []          # [end, name, child_ns, dur]

    def close(upto):
        while stack and stack[-1][0] <= upto:
            end, name, child, dur = stack.pop()
            own[name] = own.get(name, 0.0) + max(dur - child, 0.0) / 1e9
            if stack:
                stack[-1][2] += dur

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        stack.append([start + dur, name, 0.0, dur])
    close(float("inf"))
    return own


def summarize_plane(plane: dict) -> dict:
    by_line = {ln["name"]: ln["events"] for ln in plane["lines"]}
    mod_events = [e for n in MODULE_LINES for e in by_line.get(n, [])]
    op_events = [e for n in OP_LINES for e in by_line.get(n, [])]
    busy_from = op_events or mod_events
    merged = _union([[s, s + d] for _n, s, d in busy_from])
    modules: dict = {}
    for name, s, d in mod_events:
        # one jitted program compiled at several shapes is one module here:
        # drop the program id the runtime appends ("jit_step(123)")
        m = modules.setdefault(re.sub(r"\(\d+\)$", "", name), [0, 0.0])
        m[0] += 1
        m[1] += d / 1e9
    ops = sorted(_self_times(op_events).items(), key=lambda kv: -kv[1])
    return {"plane": plane["name"],
            "busy_s": sum(b - a for a, b in merged) / 1e9,
            "first_ns": merged[0][0] if merged else None,
            "last_ns": merged[-1][1] if merged else None,
            "modules": {k: {"count": v[0], "seconds": v[1]}
                        for k, v in modules.items()},
            "ops": ops[:10],
            "lines": {ln["name"]: len(ln["events"])
                      for ln in plane["lines"]}}


def summarize(planes: list) -> dict:
    """Per device plane: busy seconds, modules, top ops."""
    return {"devices": [summarize_plane(p) for p in planes
                        if any(ln["events"] for ln in p["lines"])]}


def top_module(trace):
    """(name, executions, seconds) of the module with most device time,
    averaged over the chips: the cell's step program."""
    if not trace or not trace["devices"]:
        return None
    mods: dict = {}
    for dev in trace["devices"]:
        for name, m in dev["modules"].items():
            cur = mods.setdefault(name, [0, 0.0])
            cur[0] += m["count"]
            cur[1] += m["seconds"]
    if not mods:
        return None
    name = max(mods, key=lambda k: mods[k][1])
    n_dev = len(trace["devices"])
    return name, mods[name][0] / n_dev, mods[name][1] / n_dev


def step_ms(trace):
    """Device milliseconds of the step program per execution, or None."""
    top = top_module(trace)
    if top is None or top[1] <= 0:
        return None
    return top[2] / top[1] * 1e3


def main(argv) -> int:
    src, dst = argv
    doc = summarize(read_planes(src))
    with open(dst, "w") as f:
        json.dump(doc, f)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
