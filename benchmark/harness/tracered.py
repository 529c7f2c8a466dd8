"""The reduction from a profiler trace to metrics.

jax.profiler writes <dir>/plugins/profile/<time>/<host>.xplane.pb;
jax.profiler.ProfileData reads it with nothing but JAX. `load` turns it
into a plain structure, {"planes": [{"name", "lines": [{"name", "events":
[[name, start_ns, duration_ns], ...]}]}]}, which is also what the recorded
fixture under benchmark/tests/data holds, and `reduce` works on that alone.

What a v5e trace holds (looked at by hand, PR 23, PERF.md section 3): one
plane a chip, "/device:TPU:<n>". Its line "XLA Modules" has one event per
executed program, named after the jitted function ("jit_decode(<hash>)",
"jit_prefill(<hash>)"). Its line "XLA Ops" has one event per executed HLO
operation, NESTED (a `while` encloses its body's operations), each named by
its whole HLO text ("%closed_call.41 = bf16[96,8,2,128]{...} custom-call(
...)"), which `compact` cuts to "name|opcode|result type". A Pallas kernel
is an opcode `custom-call` named "closed_call.<n>" (XLA's own custom-calls
are "custom-call.<n>"); nothing in the program names one, so inside a
decode program they are told apart by what they return: the page write
returns the two pools (a tuple), the paged read one array. "Async XLA Ops"
holds DMA copies; "/host:CPU" the Python tracer's frames, which the
harness switches off (no reader uses them). Device busy time
is the union of the "XLA Ops" intervals; the window is from the first
operation's start to the last one's end on that chip.
"""

import glob
import gzip
import json
import os
import re
import shutil

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:TPU:"


CONTAINERS = ("while", "conditional", "call")
DECODE_MODULE = "jit_decode"
PALLAS_CALL = "closed_call"     # how a pallas_call's custom-call is named
_OPCODE = re.compile(r"\s([a-z][a-z\-]*)\(")
_LAYOUT = re.compile(r"\{[^}]*\}")


def compact(text: str) -> str:
    """'%name = <type>{layout} opcode(operands...)' -> 'name|opcode|type'."""
    head, sep, rest = text.partition(" = ")
    if not sep:
        return text
    found = _OPCODE.search(" " + rest)
    if found is None:
        return head.lstrip("%") + "|?|"
    result = _LAYOUT.sub("", rest[:max(0, found.start() - 1)]).strip()
    return f"{head.lstrip('%')}|{found.group(1)}|{result[:96]}"


def parts(name: str):
    """(name, opcode, result type) of a compacted operation name."""
    bits = name.split("|", 2)
    return bits if len(bits) == 3 else [name, "", ""]


def load(path: str) -> dict:
    from jax.profiler import ProfileData

    profile = ProfileData.from_file(path)
    planes = []
    for plane in profile.planes:
        lines = []
        # only a device's operations carry HLO text to cut down
        name = compact if plane.name.startswith(DEVICE_PREFIX) else str
        for line in plane.lines:
            events = [[name(ev.name), int(ev.start_ns),
                       int(ev.duration_ns)] for ev in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def device_planes(trace: dict):
    return [p for p in trace["planes"] if p["name"].startswith(DEVICE_PREFIX)
            and any(l["name"] == OPS_LINE and l["events"] for l in p["lines"])]


def line_of(plane: dict, name: str):
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def union_ns(events) -> int:
    """Total length of the union of [start, start + duration) intervals."""
    total, end = 0, None
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        stop = start + dur
        if end is None or start > end:
            total += dur
            end = stop
        elif stop > end:
            total += stop - end
            end = stop
    return total


def gaps_ns(events):
    """[(gap_ns, start_ns)] between consecutive busy intervals."""
    out, end = [], None
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        if end is not None and start > end:
            out.append((start - end, end))
        end = max(end or 0, start + dur)
    return out


def _inside(events, spans):
    """The events that lie within one of the [start, stop) spans."""
    spans = sorted(spans)
    out, i = [], 0
    for ev in sorted(events, key=lambda e: e[1]):
        while i < len(spans) and spans[i][1] <= ev[1]:
            i += 1
        if i < len(spans) and spans[i][0] <= ev[1] and ev[1] + ev[2] <= spans[i][1]:
            out.append(ev)
    return out


def kernels(plane: dict) -> dict:
    """The decode programs' Pallas kernels on one chip: {"read": [events],
    "write": [events]} and the decode programs' own events."""
    decode = [e for e in line_of(plane, MODULES_LINE)
              if e[0].startswith(DECODE_MODULE)]
    calls = _inside([e for e in line_of(plane, OPS_LINE)
                     if parts(e[0])[1] == "custom-call"
                     and parts(e[0])[0].startswith(PALLAS_CALL)],
                    [(e[1], e[1] + e[2]) for e in decode])
    return {"decode": decode,
            "write": [e for e in calls if parts(e[0])[2].startswith("(")],
            "read": [e for e in calls if not parts(e[0])[2].startswith("(")]}


def reduce(trace: dict, host_window_s: float = 0.0) -> dict:
    """Device busy time, operations, programs and kernels, averaged over
    the chips."""
    planes = device_planes(trace)
    if not planes:
        return {"devices": 0}
    n = len(planes)
    busy = window = 0.0
    ops, modules, gaps = {}, {}, []
    found = {"decode": [0.0, 0], "read": [0.0, 0], "write": [0.0, 0]}
    for plane in planes:
        events = line_of(plane, OPS_LINE)
        first = min(e[1] for e in events)
        last = max(e[1] + e[2] for e in events)
        busy += union_ns(events) / 1e9 / n
        window += (last - first) / 1e9 / n
        for name, _, dur in events:
            op, kind, result = parts(name)
            if kind in CONTAINERS:
                continue              # its body's operations are counted
            row = ops.setdefault(f"{op} {kind} {result[:48]}".strip(), [0.0, 0])
            row[0] += dur / 1e9 / n
            row[1] += 1
        for name, _, dur in line_of(plane, MODULES_LINE):
            row = modules.setdefault(name.split("(")[0],
                                     {"busy_s": 0.0, "count": 0})
            row["busy_s"] += dur / 1e9 / n
            row["count"] += 1
        for what, evs in kernels(plane).items():
            found[what][0] += sum(e[2] for e in evs) / 1e9 / n
            found[what][1] += len(evs)
        gaps.extend(gaps_ns(events))
    gaps.sort(reverse=True)
    return {
        "devices": n, "busy_s": busy, "window_s": window,
        "host_window_s": host_window_s, "modules": modules,
        # seconds and calls, a chip: the decode programs and their two
        # Pallas kernels
        "kernels": {k: {"seconds": v[0], "calls": v[1] / n}
                    for k, v in found.items()},
        "device_ops": [[k, v[0]] for k, v in sorted(
            ops.items(), key=lambda kv: -kv[1][0])[:10]],
        # what the host was doing in a gap needs annotations the program
        # does not write yet: unattributed, not guessed
        "idle_gaps": [["unattributed", g / 1e9] for g, _ in gaps[:10]],
    }


def summary(trace: dict) -> dict:
    """What one looks at by hand: every plane and line, how many events,
    the span, the names that take most time."""
    out = []
    for plane in trace["planes"]:
        lines = []
        for line in plane["lines"]:
            events = line["events"]
            if not events:
                continue
            names = {}
            for name, _, dur in events:
                names[name] = names.get(name, 0) + dur
            top = sorted(names.items(), key=lambda kv: -kv[1])[:25]
            lines.append({"line": line["name"], "events": len(events),
                          "first_ns": min(e[1] for e in events),
                          "last_ns": max(e[1] + e[2] for e in events),
                          "top": [[k, v / 1e9] for k, v in top]})
        out.append({"plane": plane["name"], "lines": lines})
    return {"planes": out}


def sample(trace: dict, seconds: float = 1.5) -> dict:
    """A test's recorded trace: on each device plane, the whole programs
    from the first one up to and with the first whole decode program (at
    most `seconds` in), each with all its operations."""
    planes = []
    for plane in device_planes(trace):
        first = min(e[1] for e in line_of(plane, OPS_LINE))
        cut = first + int(seconds * 1e9)
        whole = sorted((e for e in line_of(plane, MODULES_LINE)
                        if first < e[1] and e[1] + e[2] <= cut),
                       key=lambda e: e[1])
        decode = [e for e in whole if e[0].startswith(DECODE_MODULE)]
        if not decode:
            continue
        lo, hi = whole[0][1], decode[0][1] + decode[0][2]
        planes.append({"name": plane["name"], "lines": [
            {"name": l["name"], "events": [e for e in l["events"]
                                           if lo <= e[1] and e[1] + e[2] <= hi]}
            for l in plane["lines"] if l["name"] in (OPS_LINE, MODULES_LINE)]})
    return {"planes": planes}


def reduce_dir(trace_dir: str, host_window_s: float) -> dict:
    """Reduce the newest trace under `trace_dir`, leave a summary and a
    small sample beside it, and drop the raw files (tens of MiB)."""
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        return {"devices": 0}
    trace = load(found[-1])
    with open(trace_dir + "_summary.json", "w") as fp:
        json.dump(summary(trace), fp)
    with gzip.open(trace_dir + "_sample.json.gz", "wt") as fp:
        json.dump(sample(trace), fp)
    shutil.rmtree(trace_dir, ignore_errors=True)
    return reduce(trace, host_window_s)
