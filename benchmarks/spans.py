"""The program's own spans and scopes, read from the profiler's trace.

The program writes its host spans (`sched.<phase>` under
`sched.iteration`, `sched.first_token`, `dispatch.*`, `train.step`) as
`jax.profiler.TraceAnnotation`s and names the parts of its step
programs with `jax.named_scope`; the profiler records both in the same
`.xplane.pb`, on the same clock, as the device's operations. This
module reduces them: a span's time less what it waited for, the
device's idle seconds by the host span they fell under, the requests'
waits, and the device's own time by program and scope.

Like `devtrace`, the reduction works on plain lists, here
`(name, start_s, duration_s, stats)`, so the tests check it on
hand-made ones; `load` turns a profile directory into such lists.

What a TPU trace looks like (jax 0.9, one v5e; looked at by hand,
PERF.md section 6, PR 25): beside `devtrace`'s device planes, the plane
`/host:CPU` holds one line per host thread, and an annotation is an
event of its thread's line, named as it was opened, its keyword
arguments the event's stats. An operation's event is named by its HLO
instruction *without* its metadata; the `op_name` that holds the scopes
(`jit(_step)/while/body/closed_call/kv_write/squeeze:`, wrapped by
what transformed it: `transpose(jvp(mlp))`) is the stat `tf_op` of the
event's *metadata* record, next to `program_id`, the fingerprint in the
program's event name. `jax.profiler.ProfileData` shows an event's own
stats but not its metadata's, so `read_op_names` reads those records
from the file's protobuf wire format itself (XSpace.planes = 1;
XPlane.name = 2, .event_metadata = 4, .stat_metadata = 5;
XEventMetadata.name = 2, .stats = 5; XStat.metadata_id = 1,
.uint64_value = 3, .int64_value = 4, .str_value = 5, .ref_value = 7).

    python -m benchmarks.spans <dir>

prints the two tables of PERF.md section 5.
"""

from __future__ import annotations

import bisect
import dataclasses
import functools
import glob
import os
import re

from benchmarks import devtrace

Span = tuple[str, float, float, dict]     # name, start_s, duration_s, stats
Interval = tuple[float, float]            # start_s, end_s

# the names the program gives the parts of its step programs
# (kubeflow_tpu: serving/engine.py, models/llama.py, ops/attention.py,
# train/trainer.py)
SCOPES = ("embed", "norm", "attn_proj", "kv_write", "paged_attention",
          "prefill_append", "decode_attention", "flash_attention", "mlp",
          "head", "sample", "loss", "optimizer")
UNSCOPED = "unscoped"
OUTSIDE = "outside any span"
SPAN_PREFIXES = ("sched.", "dispatch.", "train.")

WRAPPED = re.compile(r"^(?:[\w.\-]+\()*([^()]*)\)*$")
# (program fingerprint, operation's event name) -> its op_name
OpNames = dict[tuple[int, str], str]


# -- host spans -------------------------------------------------------------

def worker_line(lines: list[list[Span]], anchor: str) -> list[Span]:
    """The line of the one task that opens the nested spans: the one
    that holds most spans named `anchor`. Empty where none does."""
    best = max(lines, default=[],
               key=lambda line: sum(s[0] == anchor for s in line))
    return best if any(s[0] == anchor for s in best) else []


def span_less(line: list[Span], name: str, minus: list[str]) -> list[float]:
    """For each span called `name` on `line`: its duration less the
    time of the spans called one of `minus` that lie inside it. An
    iteration less the phases that wait on the device is the time the
    host itself took. Spans of other lines (the executor's threads)
    are not `line`'s and take nothing away."""
    inner = sorted((s for s in line if s[0] in minus), key=lambda s: s[1])
    starts = [s[1] for s in inner]
    out = []
    for n, start, dur, _ in line:
        if n != name:
            continue
        left = dur
        for _, s0, d0, _ in inner[bisect.bisect_left(starts, start):]:
            if s0 >= start + dur:
                break
            left -= min(s0 + d0, start + dur) - s0
        out.append(max(0.0, left))
    return out


def gaps_between(line: list[Span], name: str) -> list[float]:
    """Seconds from the end of each span called `name` to the start of
    the next: for `train.step`, the trainer's host gap."""
    spans = sorted((s for s in line if s[0] == name), key=lambda s: s[1])
    return [max(0.0, b[1] - (a[1] + a[2])) for a, b in zip(spans, spans[1:])]


def stat_values(spans: list[Span], name: str, stat: str) -> list[float]:
    return [float(s[3][stat]) for s in spans if s[0] == name and stat in s[3]]


def innermost_segments(line: list[Span]) -> list[tuple[float, float, str]]:
    """`line`'s nested spans as disjoint `(start, end, name)` pieces,
    each named by the innermost span that covers it."""
    out: list[tuple[float, float, str]] = []
    stack: list[list] = []           # open spans: [name, end, covered to]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            name, end, cursor = stack.pop()
            if end > cursor:
                out.append((cursor, end, name))
            if stack:
                stack[-1][2] = max(stack[-1][2], end)

    for name, start, dur, _ in sorted(line, key=lambda s: (s[1], -s[2])):
        close(start)
        if stack and start > stack[-1][2]:
            out.append((stack[-1][2], start, stack[-1][0]))
            stack[-1][2] = start
        stack.append([name, start + dur, start])
    close(float("inf"))
    return out


def gap_segments(line: list[Span], name: str,
                 label: str) -> list[tuple[float, float, str]]:
    """Whatever of `line`'s time is not inside a span called `name`, as
    pieces called `label`: the trainer's anatomy is `step` and
    `host_gap`, and the gap has no span of its own, it is what lies
    before, between and after the `train.step` spans."""
    spans = sorted((s for s in line if s[0] == name), key=lambda s: s[1])
    if not spans:
        return []
    edges = ([float("-inf")] + [t for _, start, dur, _ in spans
                                for t in (start, start + dur)]
             + [float("inf")])
    return [(a, b, label) for a, b in zip(edges[::2], edges[1::2]) if b > a]


def worker_segments(lines: list[list[Span]], anchor: str | None = None
                    ) -> list[tuple[float, float, str]]:
    """What the worker was doing, piece by piece: the batcher's line
    (`sched.iteration`) or the trainer's (`train.step`, whose gaps are
    `train.host_gap`), whichever the profile holds."""
    for name in ([anchor] if anchor else ["sched.iteration", "train.step"]):
        line = worker_line(lines, name)
        if line:
            return sorted(innermost_segments(line)
                          + gap_segments(line, "train.step",
                                         "train.host_gap"))
    return []


# -- the device's idle time, by what the host was doing ---------------------

def idle_intervals(ops: list[devtrace.Event],
                   programs: list[devtrace.Event]) -> list[Interval]:
    """The complement of the union of the operations' intervals,
    between the first program's start and the last one's end."""
    if not ops or not programs:
        return []
    lo = min(start for _, start, _ in programs)
    hi = max(start + dur for _, start, dur in programs)
    out, end = [], lo
    for _, start, dur in sorted(ops, key=lambda e: e[1]):
        if start > end:
            out.append((end, min(start, hi)))
        end = max(end, start + dur)
        if end >= hi:
            break
    if end < hi:
        out.append((end, hi))
    return [(a, b) for a, b in out if b > a]


def idle_by_span(idle: list[Interval],
                 segments: list[tuple[float, float, str]]
                 ) -> dict[str, float]:
    """Idle seconds cut by the segment that covers them; what no
    segment covers is `OUTSIDE`."""
    segments = sorted(segments)
    starts = [s for s, _, _ in segments]
    out: dict[str, float] = {}
    for a, b in idle:
        covered = 0.0
        i = max(0, bisect.bisect_right(starts, a) - 1)
        while i < len(segments) and segments[i][0] < b:
            s, e, name = segments[i]
            over = min(b, e) - max(a, s)
            if over > 0:
                out[name] = out.get(name, 0.0) + over
                covered += over
            i += 1
        if b - a - covered > 0:
            out[OUTSIDE] = out.get(OUTSIDE, 0.0) + (b - a - covered)
    return out


# -- the device's own time, by program and scope ----------------------------

def op_scope(op_name: str | None,
             scopes: tuple[str, ...] = SCOPES) -> str:
    """The outermost of `scopes` among the path components of an
    operation's `op_name`, whatever wraps it (`transpose(jvp(mlp))`, a
    `while`'s `body`); `UNSCOPED` where there is none, or no `op_name`
    at all (a `while` itself, a copy the compiler put in)."""
    for part in (op_name or "").split("/"):
        w = WRAPPED.match(part)
        if w and w.group(1) in scopes:
            return w.group(1)
    return UNSCOPED


def fingerprint(program_event: str) -> int | None:
    """`jit__step(1234567)` -> 1234567."""
    m = re.search(r"\((\d+)\)$", program_event)
    return int(m.group(1)) if m else None


def scope_seconds(ops: list[devtrace.Event], programs: list[devtrace.Event],
                  op_names: OpNames, scopes: tuple[str, ...] = SCOPES
                  ) -> dict[str, dict[str, float]]:
    """program -> scope -> the seconds its operations took themselves
    (`devtrace.self_time`: a `while` does not count its body again).
    An operation belongs to the program that was running when it
    started (`devtrace.label_by_program`'s rule), and two programs name
    their operations alike, so its `op_name` is looked up under that
    program's fingerprint."""
    progs = sorted(programs, key=lambda e: e[1])
    starts = [start for _, start, _ in progs]
    out: dict[str, dict[str, float]] = {}
    # in whole nanoseconds, the trace's own unit: an operation starts
    # in the very nanosecond its predecessor ends, and in float seconds
    # that end can round to after the start, which would make the one
    # the other's child
    own_ns = devtrace.self_time(
        [(name, round(start * 1e9), round(dur * 1e9))
         for name, start, dur in ops])
    for (name, start, _), (_, _, own) in zip(ops, own_ns):
        i = bisect.bisect_right(starts, start) - 1
        inside = i >= 0 and start <= progs[i][1] + progs[i][2]
        prog = progs[i][0] if inside else "?"
        scope = op_scope(op_names.get((fingerprint(prog), name)), scopes)
        by_scope = out.setdefault(devtrace.program_name(prog), {})
        by_scope[scope] = by_scope.get(scope, 0.0) + own * 1e-9
    return out


# -- one profile, read once -------------------------------------------------

@dataclasses.dataclass
class Profile:
    programs: list[devtrace.Event]         # chip 0's executions
    ops: list[devtrace.Event]              # chip 0's operations
    lines: list[list[Span]]                # the host's threads, our spans
    op_names: OpNames = dataclasses.field(default_factory=dict)

    @functools.cached_property
    def by_scope(self) -> dict[str, dict[str, float]]:
        return scope_seconds(self.ops, self.programs, self.op_names)

    def executions(self, program: str) -> int:
        return sum(devtrace.program_name(n) == program
                   for n, _, _ in self.programs)

    def window_s(self) -> float:
        """First program's start to last program's end, by the trace's
        own clock."""
        if not self.programs:
            return 0.0
        return (max(s + d for _, s, d in self.programs)
                - min(s for _, s, _ in self.programs))


def newest_xplane(trace_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    return found[-1] if found else None


def read_host_spans(xplane: str) -> list[list[Span]]:
    """The annotations the program opened, by host thread."""
    from jax.profiler import ProfileData

    lines = []
    for plane in ProfileData.from_file(xplane).planes:
        if devtrace.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            spans = [(ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9,
                      dict(ev.stats))
                     for ev in line.events
                     if ev.name.startswith(SPAN_PREFIXES)]
            if spans:
                lines.append(spans)
    return lines


def _varint(buf, i: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return value, i


def _fields(buf):
    """(field number, wire type, value) of one protobuf message: a
    varint as an int, a length-delimited field as a view of its bytes."""
    i, n = 0, len(buf)
    while i < n:
        tag, i = _varint(buf, i)
        field, wire = tag >> 3, tag & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire in (1, 2, 5):
            size, i = _varint(buf, i) if wire == 2 else ({1: 8, 5: 4}[wire], i)
            value = buf[i:i + size]
            i += size
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield field, wire, value


def read_op_names(xplane: str) -> OpNames:
    """The `tf_op` and `program_id` of every operation's metadata
    record on the device planes (the module docstring says why by
    hand). Empty where the file holds none."""
    with open(xplane, "rb") as f:
        space = memoryview(f.read())
    out: OpNames = {}
    for field, _, plane in _fields(space):
        if field != 1:
            continue
        name, records, stat_names = "", [], {}
        for pf, _, value in _fields(plane):
            if pf == 2:
                name = bytes(value).decode()
            elif pf == 4:                  # map entry: key = 1, value = 2
                records += [v for k, _, v in _fields(value) if k == 2]
            elif pf == 5:
                entry = {k: v for k, _, v in _fields(value)}
                meta = {k: v for k, _, v in _fields(entry.get(2, b""))}
                stat_names[entry.get(1)] = bytes(meta.get(2, b"")).decode()
        if not devtrace.DEVICE_PLANE.match(name):
            continue
        for record in records:
            event_name, stats = "", {}
            for rf, _, value in _fields(record):
                if rf == 2:
                    event_name = bytes(value).decode()
                elif rf == 5:
                    stat = {k: v for k, _, v in _fields(value)}
                    stats[stat_names.get(stat.get(1))] = stat
            op, prog = stats.get("tf_op"), stats.get("program_id")
            if op is None or prog is None:
                continue
            op_name = (bytes(op[5]).decode() if 5 in op
                       else stat_names.get(op.get(7), ""))
            out[(prog.get(3, prog.get(4)), event_name)] = op_name
    return out


_loaded: dict[str, tuple[float, Profile]] = {}


def load(trace_dir: str) -> Profile | None:
    """The newest profile under `trace_dir`, parsed once however many
    readers ask (kept by path and modification time). None where there
    is no profile, or no operation ran on a device."""
    xplane = newest_xplane(trace_dir)
    if xplane is None:
        return None
    mtime = os.path.getmtime(xplane)
    if _loaded.get(xplane, (None,))[0] != mtime:
        chips = [c for c in devtrace.read_xplane(trace_dir) if c[1]]
        programs, ops = chips[0] if chips else ([], [])
        _loaded[xplane] = (mtime, Profile(
            programs=programs, ops=ops, lines=read_host_spans(xplane),
            op_names=read_op_names(xplane)))
    profile = _loaded[xplane][1]
    return profile if profile.ops else None


# -- the tables of PERF.md section 5 ----------------------------------------

def tables(profile: Profile) -> list[str]:
    out = ["device time by program and scope (own seconds, share of the "
           "program)"]
    by_prog = profile.by_scope
    totals = {p: sum(v.values()) for p, v in by_prog.items()}
    for prog in sorted(totals, key=lambda p: -totals[p])[:6]:
        runs = profile.executions(prog)
        out.append(f"  {prog}: {totals[prog]:.4f} s in {runs} executions")
        for scope, secs in sorted(by_prog[prog].items(),
                                  key=lambda kv: -kv[1]):
            out.append(f"    {scope:18s} {secs:9.4f} s "
                       f"{100 * secs / totals[prog]:6.2f} %")
    idle = idle_intervals(profile.ops, profile.programs)
    total_idle = sum(b - a for a, b in idle)
    out.append(f"idle seconds by host span (device idle {total_idle:.4f} s "
               f"of a window of {profile.window_s():.4f} s)")
    by_span = idle_by_span(idle, worker_segments(profile.lines))
    for name, secs in sorted(by_span.items(), key=lambda kv: -kv[1]):
        out.append(f"  {name:24s} {secs:9.4f} s "
                   f"{100 * secs / total_idle:6.2f} %")
    return out


if __name__ == "__main__":
    import sys

    prof = load(sys.argv[1])
    print("\n".join(tables(prof)) if prof else "no device plane")
