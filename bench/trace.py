"""From a profiler trace to device busy time, kernel time and idle gaps.

:func:`export` turns the profiler's ``.xplane.pb`` into a small plain
record — the traced window, every device operation of each chip and the
benchmark's own host spans — and everything after it is plain Python over
that record, so the tests check the reduction on a recorded trace without
a chip.
"""
from __future__ import annotations

import glob
import re

__all__ = [
    "op_record", "export", "union", "busy_ns", "classify", "summarize", "top_ops",
    "idle_gaps", "KERNEL_PATTERNS", "COLLECTIVE_PATTERN",
]

#: the line of a device plane that holds one event per operation
DEVICE_LINE = "XLA Ops"
#: host spans written by the benchmark (jax.profiler.TraceAnnotation)
HOST_PREFIX = "bench."
#: the span that bounds the traced window
WINDOW_SPAN = "bench.traced"

#: which device operations are the SpMM kernels: the instruction names
#: that the program's kernel wrappers give their calls
KERNEL_PATTERNS = {
    "forward": re.compile(r"frontier_spmm"),
    "backward": re.compile(r"dependency_spmm"),
}
#: opcodes of the operations that exchange data between chips
COLLECTIVE_PATTERN = re.compile(
    r"(all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all|"
    r"send|recv)(-start|-done)?$")


#: operations that only contain others on the same line (a loop, a
#: branch, a call): their time is counted through what they contain
CONTAINER_OPCODES = frozenset({"while", "conditional", "call"})


def op_record(text: str, start_ns, duration_ns) -> list:
    """``[instruction, start_ns, duration_ns, opcode]`` of a device event
    whose name is the HLO instruction (``%name.3 = <shape> opcode(...)``)."""
    m = re.match(r"%?([\w.\-]+)(?: = (.*))?$", text, re.DOTALL)
    if not m:
        return [text[:80], int(start_ns), int(duration_ns), ""]
    name, rest = m.group(1), m.group(2) or ""
    opcode = ""
    if rest:
        if rest.startswith("("):  # tuple shape: skip to its closing paren
            depth = 0
            for i, ch in enumerate(rest):
                depth += ch == "("
                depth -= ch == ")"
                if depth == 0:
                    rest = rest[i + 1:]
                    break
        else:
            rest = rest.split(" ", 1)[1] if " " in rest else ""
        opcode = re.match(r"\s*([\w\-]*)", rest).group(1)
    return [name, int(start_ns), int(duration_ns), opcode]


def export(trace_dir: str) -> dict:
    """The plain record of the newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    devices: dict[str, list] = {}
    host: list = []
    for plane in data.planes:
        m = re.fullmatch(r"/device:(TPU:\d+)", plane.name)
        for line in plane.lines:
            if m and line.name == DEVICE_LINE:
                ops = devices.setdefault(m.group(1), [])
                for ev in line.events:
                    ops.append(op_record(ev.name, ev.start_ns, ev.duration_ns))
            elif not m:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        host.append([ev.name, int(ev.start_ns), int(ev.duration_ns)])
    window = [h for h in host if h[0] == WINDOW_SPAN]
    if not window:
        raise ValueError(f"the trace has no {WINDOW_SPAN!r} span")
    _, start, dur = window[0]
    return {"window": [start, start + dur], "devices": devices, "host": host}


def union(intervals) -> list[tuple[int, int]]:
    """Merged, sorted (start, end) intervals."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def busy_ns(ops, window) -> int:
    """Length of the union of the operations' intervals inside the window."""
    lo, hi = window
    return sum(e - s for s, e in union(clip(((o[1], o[1] + o[2]) for o in ops), lo, hi)))


def classify(op) -> str:
    """``forward`` / ``backward`` (the SpMM kernels), ``collective``,
    ``container`` or ``other``."""
    name, opcode = op[0], op[3]
    for kind, pattern in KERNEL_PATTERNS.items():
        if pattern.match(name):
            return kind
    if opcode in CONTAINER_OPCODES:
        return "container"
    if COLLECTIVE_PATTERN.match(opcode):
        return "collective"
    return "other"


def summarize(record: dict) -> dict:
    """Per-chip busy, kernel and collective nanoseconds inside the window.

    ``collective_exposed_ns`` is the time of collective operations during
    which no other operation ran on that chip.
    """
    lo, hi = record["window"]
    chips = {}
    for dev, ops in sorted(record["devices"].items()):
        inside = [o for o in ops if o[1] + o[2] > lo and o[1] < hi]
        kinds = {"forward": [], "backward": [], "collective": [], "container": [],
                 "other": []}
        for o in inside:
            kinds[classify(o)].append(o)
        compute = union(clip(
            ((o[1], o[1] + o[2]) for k in ("forward", "backward", "other") for o in kinds[k]),
            lo, hi))
        coll = union(clip(((o[1], o[1] + o[2]) for o in kinds["collective"]), lo, hi))
        chips[dev] = {
            "busy_ns": busy_ns(inside, (lo, hi)),
            "forward_ns": busy_ns(kinds["forward"], (lo, hi)),
            "backward_ns": busy_ns(kinds["backward"], (lo, hi)),
            "forward_calls": len(kinds["forward"]),
            "backward_calls": len(kinds["backward"]),
            "collective_ns": sum(e - s for s, e in coll),
            "collective_exposed_ns": _minus(coll, compute),
        }
    return {"window_ns": hi - lo, "chips": chips}


def _minus(a, b) -> int:
    """Length of the union ``a`` less what the union ``b`` covers."""
    total = sum(e - s for s, e in a)
    for s, e in a:
        for bs, be in b:
            if be <= s:
                continue
            if bs >= e:
                break
            total -= min(e, be) - max(s, bs)
    return total


def top_ops(record: dict, limit: int = 10) -> list:
    """The device operations that took most time in the window, summed
    over chips and over the instructions of one name, in seconds.  Loops
    and other containers are left out: their time is their contents'."""
    lo, hi = record["window"]
    acc: dict[str, int] = {}
    for ops in record["devices"].values():
        for o in ops:
            s, e = max(o[1], lo), min(o[1] + o[2], hi)
            kind = classify(o)
            if e > s and kind != "container":
                key = re.sub(r"\.\d+$", "", o[0])
                acc[key] = acc.get(key, 0) + (e - s)
    return [[k, v / 1e9] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:limit]]


def idle_gaps(record: dict, limit: int = 10) -> list:
    """The longest gaps in which no chip ran anything, in seconds, each
    named by the benchmark host span that covers at least half of it;
    ``driver+<span>`` when a span falls inside the gap but covers less
    (the round loop's drain and dispatch around it), ``driver`` when none
    does."""
    lo, hi = record["window"]
    busy = union(clip(
        ((o[1], o[1] + o[2]) for ops in record["devices"].values() for o in ops), lo, hi))
    gaps, cur = [], lo
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        gaps.append((cur, hi))
    spans = [(h[0], h[1], h[1] + h[2]) for h in record["host"]
             if h[0] not in (WINDOW_SPAN, "bench.entry")]
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:limit]:
        best, name = 0, None
        for label, hs, he in spans:
            cover = min(e, he) - max(s, hs)
            if cover > best:
                best, name = cover, label.removeprefix(HOST_PREFIX)
        if name is None:
            name = "driver"
        elif 2 * best < e - s:
            name = f"driver+{name}"
        out.append([name, (e - s) / 1e9])
    return out
