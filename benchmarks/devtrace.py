"""From a profiler trace to device numbers: busy time, time per
executed program, the operations that took most time, the idle gaps.

The reduction works on plain `(name, start_s, duration_s)` lists, so
the tests check it on hand-made lists; `read_xplane` turns an
`.xplane.pb` into such lists with `jax.profiler.ProfileData` alone.

What a TPU trace looks like (jax 0.9, one v5e; looked at by hand,
PERF.md section 6, PR 24): one plane per chip named `/device:TPU:<n>`;
its line `XLA Modules` holds one event per executed program, named
`<jit name>(<fingerprint>)`; its line `XLA Ops` holds one event per
operation of those programs, named by its whole HLO instruction and
nested (a `while` holds its body's operations). Other planes are host
threads.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
import statistics

Event = tuple[str, float, float]          # name, start_s, duration_s

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
PROGRAM_LINE = "XLA Modules"
OP_LINE = "XLA Ops"


def union_seconds(events: list[Event]) -> float:
    """Seconds covered by at least one event: overlaps count once."""
    total, end = 0.0, float("-inf")
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        stop = start + dur
        if start > end:
            total += dur
            end = stop
        elif stop > end:
            total += stop - end
            end = stop
    return total


def program_name(event_name: str) -> str:
    """`jit__step(1234567)` -> `jit__step`: the fingerprint changes
    with every edit of the program, the name does not."""
    return event_name.split("(", 1)[0]


OP_HEAD = re.compile(r"^(%[\w.\-]+) = .*?\s([a-z][\w\-]*)\(")


def short_op_name(event_name: str) -> str:
    """An operation's event name is its whole HLO instruction, often
    thousands of characters: keep `%name opcode`."""
    m = OP_HEAD.match(event_name)
    return f"{m.group(1)} {m.group(2)}" if m else event_name[:80]


def self_time(ops: list[Event]) -> list[Event]:
    """The operations line nests: a `while` holds its body's operations,
    a call its callee's. Each event's own time is its duration less its
    direct children's, so that a sum over events counts nothing twice."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    own = [dur for _, _, dur in ops]
    stack: list[int] = []                  # open events, innermost last
    for i in order:
        _, start, dur = ops[i]
        while stack and ops[stack[-1]][1] + ops[stack[-1]][2] <= start:
            stack.pop()
        if stack:
            own[stack[-1]] -= dur
        stack.append(i)
    return [(name, start, max(0.0, o))
            for (name, start, _), o in zip(ops, own)]


def label_by_program(ops: list[Event], programs: list[Event]) -> list[Event]:
    """`<program>: %name opcode`: two programs number their operations
    alike, so an operation is named with the program that ran it."""
    progs = sorted(programs, key=lambda e: e[1])
    starts = [start for _, start, _ in progs]
    out = []
    for name, start, dur in ops:
        i = bisect.bisect_right(starts, start) - 1
        inside = i >= 0 and start <= progs[i][1] + progs[i][2]
        prog = program_name(progs[i][0]) if inside else "?"
        out.append((f"{prog}: {short_op_name(name)}", start, dur))
    return out


def by_program(programs: list[Event]) -> dict[str, list[float]]:
    out: dict[str, list[float]] = {}
    for name, _, dur in programs:
        out.setdefault(program_name(name), []).append(dur)
    return out


def top_total(events: list[Event], n: int = 10) -> list[list]:
    """The `n` names with most summed time, most first (ties by name)."""
    total: dict[str, float] = {}
    for name, _, dur in events:
        total[name] = total.get(name, 0.0) + dur
    ranked = sorted(total.items(), key=lambda kv: (-kv[1], kv[0]))
    return [[name, secs] for name, secs in ranked[:n]]


def idle_gaps(programs: list[Event], n: int = 10) -> list[list]:
    """Idle time between consecutive program executions, summed by the
    program that ended the gap (`before <name>`): what the device was
    waiting for the host to send. Most first."""
    gaps: list[Event] = []
    end = None
    for name, start, dur in sorted(programs, key=lambda e: e[1]):
        if end is not None and start > end:
            gaps.append((f"before {program_name(name)}", end, start - end))
        end = start + dur if end is None else max(end, start + dur)
    return top_total(gaps, n)


@dataclasses.dataclass
class DeviceTrace:
    """One traced window, reduced. Times in seconds; `busy_s` is the
    mean over the chips that ran anything."""
    window_s: float
    busy_s: float
    programs: dict[str, list[float]]       # chip 0's executions by name
    device_ops: list[list]                 # [[name, own seconds], ...]
    idle_gaps: list[list]

    def program_total_s(self, name: str) -> float | None:
        runs = self.programs.get(name)
        return sum(runs) if runs else None

    def program_median_s(self, name: str) -> float | None:
        runs = self.programs.get(name)
        return statistics.median(runs) if runs else None


def reduce(chips: list[tuple[list[Event], list[Event]]],
           window_s: float) -> DeviceTrace | None:
    """`chips`: for each chip its (program events, operation events).
    None where no operation ran on any device."""
    chips = [(progs, ops) for progs, ops in chips if ops]
    if not chips:
        return None
    busy = [union_seconds(ops) for _, ops in chips]
    progs0, ops0 = chips[0]
    return DeviceTrace(
        window_s=window_s, busy_s=sum(busy) / len(busy),
        programs=by_program(progs0),
        device_ops=top_total(label_by_program(self_time(ops0), progs0)),
        idle_gaps=idle_gaps(progs0))


def _planes(trace_dir: str) -> list:
    """The planes of the newest `.xplane.pb` under `trace_dir`."""
    from jax.profiler import ProfileData

    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    return list(ProfileData.from_file(found[-1]).planes) if found else []


def read_xplane(trace_dir: str) -> list[tuple[list[Event], list[Event]]]:
    """A profile directory's device planes, as `reduce` wants them."""
    chips = []
    for plane in _planes(trace_dir):
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = {PROGRAM_LINE: [], OP_LINE: []}
        for line in plane.lines:
            if line.name in lines:
                lines[line.name] = [
                    (ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9)
                    for ev in line.events]
        chips.append((lines[PROGRAM_LINE], lines[OP_LINE]))
    return chips


def describe(trace_dir: str, events: int = 5) -> list[str]:
    """Planes, lines and the first few events of each: for looking at a
    trace by hand (`python -m benchmarks.devtrace <dir>`)."""
    out = []
    for plane in _planes(trace_dir):
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            out.append(f"  line {line.name!r}: {len(evs)} events")
            for ev in evs[:events]:
                out.append(f"    {ev.name!r} start_ns={ev.start_ns} "
                           f"duration_ns={ev.duration_ns}")
    return out


if __name__ == "__main__":
    import sys

    print("\n".join(describe(sys.argv[1])))
