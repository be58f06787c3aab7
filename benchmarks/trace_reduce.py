"""From a profiler trace (`.xplane.pb`) and the compiled step's HLO text to
the numbers the per-layer metrics read. One reduction, kept with the
benchmark, so that every PR computes the same number in the same way.

What a TPU trace holds (read by hand from a v5e trace before this was
written; `testdata/` keeps a recorded one): one plane per chip named
`/device:TPU:<n>`, whose line `XLA Ops` has one event per executed HLO
instruction, named as in the compiled module (`fusion.12`, `all-gather.3`,
`copy-start.5`; a Mosaic call carries the name of the jaxpr equation around
it, such as `closed_call.9`). A `while` is one long event with its body's
events nested inside. Line `XLA Modules` has one event per program run. The
plane `/host:CPU` has one line per host thread; `jax.profiler.TraceAnnotation`
spans (`bench/...`) are events there, on a clock about a millisecond off the
device's.

`ProfileData` gives an event's own stats but not its metadata's, where the
profiler keeps the HLO category. So what an instruction IS comes from the
compiled HLO text, which the benchmark has anyway (`parse_hlo`):

    matmul      a convolution or dot, or a fusion that holds one. On a mesh
                XLA fuses an all-gather INTO some of these
                (async_collective_fusion); that collective's time cannot be
                told from the matmul's and counts here.
    mosaic      custom-call to tpu_custom_call: a Pallas kernel. Which kernel
                it is a reader tells from the shapes of the call's operands
                and results, never from a function name of the program's:
                a renamed, merged or split kernel is still found.
    collective  all-reduce, all-gather, reduce-scatter, all-to-all,
                collective-permute, their -start/-done, or a fusion holding
                one and no matmul.
    container   while, conditional, call: their bodies' events carry the time
    other       everything else

Times are self times: an event's duration less its children's, so that
nothing is counted twice. Busy time is the union of all `XLA Ops` events.
Ops on one chip's line run one at a time, so a collective that is an event
of its own blocks the core for its self time: that is its exposed time. The
hidden part is what an asynchronous pair overlaps: from the end of a -start
to the beginning of its -done.
"""
from __future__ import annotations

import collections
import dataclasses
import re

DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
HOST_PLANE = "/host:CPU"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
SPAN_PREFIX = "bench/"
WINDOW_SPAN = "bench/window"

COLLECTIVE_OPCODES = frozenset(
    base + suffix
    for base in ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                 "collective-permute", "collective-broadcast")
    for suffix in ("", "-start", "-done"))
MATMUL_OPCODES = frozenset(("convolution", "dot"))
CONTAINER_OPCODES = frozenset(("while", "conditional", "call"))
MOSAIC_TARGET = "tpu_custom_call"

_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = (.*)$")
_OPCODE = re.compile(r"(?:^|[ )])([a-z][a-z0-9\-]*)\(")
_CALLED = re.compile(
    r"(?:calls|body|condition|to_apply|branch_computations)="
    r"\{?%?([\w.\-]+(?:, ?%?[\w.\-]+)*)\}?")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_FIRST_OPERAND = re.compile(r"\(%?([\w.\-]+)")
_SHAPE = re.compile(r"\b[a-z]+\d+\[(\d+(?:,\d+)*)\]")
_OPERAND_LAYOUTS = "operand_layout_constraints={"
REMAT_SCOPE = "rematted_computation"    # JAX's name for what remat repeats


@dataclasses.dataclass(frozen=True)
class Op:
    """One instruction of the compiled module, as the reduction needs it."""
    opcode: str
    category: str
    label: str                      # "<category>: <where it came from>"
    shapes: frozenset = frozenset()     # dims ("32,16,512,512") of every
    #                                     array in it and in what it fuses
    remat: bool = False                 # repeats the forward for the backward
    operands: tuple = ()                # a Mosaic call's: dims of each
    results: tuple = ()
    waits_for: str | None = None        # a -done's -start


def _dims(text: str) -> tuple:
    """Every array shape in a piece of HLO text, as a tuple of ints each."""
    return tuple(tuple(int(d) for d in m.split(","))
                 for m in _SHAPE.findall(text))


def _braced(text: str, opening: str) -> str:
    """What stands between `opening` (which ends in a brace) and the brace
    that closes it; empty if `opening` is not there."""
    start = text.find(opening)
    if start < 0:
        return ""
    start += len(opening)
    depth = 1
    for i in range(start, len(text)):
        depth += {"{": 1, "}": -1}.get(text[i], 0)
        if not depth:
            return text[start:i]
    return text[start:]


def parse_hlo(text: str) -> dict:
    """{instruction name: Op} for every instruction of every computation."""
    raw, computations, current = {}, collections.defaultdict(list), None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            current = m.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if not (m and current):
            continue
        name, rest = m.groups()
        opcode = _OPCODE.search(rest)
        if not opcode:
            continue
        called = [c.strip().lstrip("%") for group in _CALLED.findall(rest)
                  for c in group.split(",")]
        target = _TARGET.search(rest)
        # what the instruction says of itself ends where its metadata begins
        # (a Mosaic call's serialized body, megabytes, comes after)
        own = rest.split(", metadata=", 1)[0].split(", backend_config=", 1)[0]
        raw[name] = (opcode.group(1), rest, called,
                     target.group(1) if target else None, opcode, own)
        computations[current].append(name)

    def inside(computation, seen):
        """(opcodes, custom-call targets, shapes) of a computation and all
        it calls."""
        opcodes, targets, shapes = set(), set(), set()
        for name in computations.get(computation, ()):
            opcode, _, called, target, _, own = raw[name]
            opcodes.add(opcode)
            shapes.update(_SHAPE.findall(own))
            if target:
                targets.add(target)
            for c in called:
                if c not in seen:
                    seen.add(c)
                    o, t, s = inside(c, seen)
                    opcodes |= o
                    targets |= t
                    shapes |= s
        return opcodes, targets, shapes

    ops = {}
    for name, (opcode, rest, called, target, at, own) in raw.items():
        opcodes, targets = {opcode}, {target} if target else set()
        shapes = set(_SHAPE.findall(own))
        if opcode == "fusion":
            for c in called:
                o, t, s = inside(c, {c})
                opcodes |= o
                targets |= t
                shapes |= s
        operands = results = ()
        if opcode in CONTAINER_OPCODES:
            category = "container"
        elif opcodes & MATMUL_OPCODES:
            category = "matmul"
        elif MOSAIC_TARGET in targets:
            category = "mosaic"
            operands = _dims(_braced(own, _OPERAND_LAYOUTS))
            results = _dims(rest[:at.start()])
        elif opcodes & COLLECTIVE_OPCODES:
            category = "collective"
        else:
            category = "other"
        waits_for = None
        if opcode.endswith("-done"):
            operand = _FIRST_OPERAND.match(rest[at.end() - 1:])
            waits_for = operand.group(1) if operand else None
        op_name = _OP_NAME.search(rest)
        where = op_name.group(1) if op_name else opcode
        ops[name] = Op(opcode, category, f"{category}: {_short(where)}",
                       frozenset(shapes), REMAT_SCOPE in where, operands,
                       results, waits_for)
    return ops


def _short(op_name: str, keep: int = 3) -> str:
    """The last few scopes of a jaxpr path: enough to say what the op is."""
    parts = [p for p in op_name.split("/") if p]
    return "/".join(parts[-keep:])


# ------------------------------------------------------------------ loading

@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float        # ns on the trace's clock
    end: float


@dataclasses.dataclass
class Trace:
    ops: dict           # device plane name -> [Event] of its XLA Ops line
    modules: dict       # device plane name -> [Event] of its XLA Modules
    host: list          # [Event]: the benchmark's own spans (bench/...)


def _events(line):
    return [Event(_instruction_name(e.name), e.start_ns,
                  e.start_ns + e.duration_ns) for e in line.events]


def _instruction_name(event_name: str) -> str:
    """`%fusion.12 = bf16[..] fusion(..)` or `fusion.12` -> `fusion.12`."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def load(path: str) -> Trace:
    """Read an `.xplane.pb` with nothing but JAX."""
    from jax.profiler import ProfileData
    return from_profile(ProfileData.from_file(path))


def from_profile(profile) -> Trace:
    trace = Trace({}, {}, [])
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    trace.ops[plane.name] = _events(line)
                elif line.name == MODULES_LINE:
                    trace.modules[plane.name] = _events(line)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                trace.host += [e for e in _events(line)
                               if e.name.startswith(SPAN_PREFIX)]
    return trace


# ---------------------------------------------------------------- reduction

@dataclasses.dataclass
class Chip:
    """One chip's share of the window."""
    busy_s: float
    category_s: dict            # category -> self seconds
    op_s: dict                  # instruction name -> self seconds
    op_calls: dict              # instruction name -> events
    collective_exposed_s: float
    collective_hidden_s: float
    gaps: list                  # [(start ns, end ns)] with no op running


@dataclasses.dataclass
class Summary:
    steps: int
    chips: int
    window_s: float
    busy_s: float               # mean over the chips
    category_s: dict            # mean over the chips
    ops: dict                   # instruction name -> Op (`parse_hlo`)
    op_s: dict                  # instruction name -> self seconds, mean
    op_calls: dict              # instruction name -> events, mean
    collective_s: float         # exposed + hidden, on the worst chip
    collective_exposed_s: float     # on the worst chip
    device_ops: list            # [[label, seconds]] most time first, <= 10
    idle_gaps: list             # [[what the host did, seconds]] <= 10

    def seconds(self, wanted) -> float:
        """Self seconds of the instructions whose Op `wanted` accepts."""
        return sum(s for name, s in self.op_s.items()
                   if name in self.ops and wanted(self.ops[name]))


def _union(intervals):
    """Sorted, merged [(start, end)]."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def _reduce_chip(events, ops, window) -> Chip:
    w0, w1 = window
    events = sorted(events, key=lambda e: (e.start, -e.end))
    # self time: an event's children are the events nested directly in it
    child = [0.0] * len(events)
    stack = []
    for i, e in enumerate(events):
        while stack and events[stack[-1]].end <= e.start:
            stack.pop()
        if stack:
            child[stack[-1]] += e.end - e.start
        stack.append(i)
    category_s = collections.defaultdict(float)
    op_s = collections.defaultdict(float)
    op_calls = collections.defaultdict(int)
    exposed = hidden = 0.0
    started = {}                            # -start name -> its last end
    for e, c in zip(events, child):
        op = ops.get(e.name)
        own = max(e.end - e.start - c, 0.0) * 1e-9
        category = op.category if op else "other"
        if category == "container":
            category = "other"      # what a loop spends outside its body
        category_s[category] += own
        op_s[e.name] += own
        op_calls[e.name] += 1
        if category == "collective":
            exposed += own
            if op.opcode.endswith("-start"):
                started[e.name] = e.end
            elif op.waits_for in started:
                hidden += max(e.start - started.pop(op.waits_for), 0.0) * 1e-9
    busy = _union((e.start, e.end) for e in events)
    edges = [w0] + [t for iv in busy for t in iv] + [w1]
    gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    return Chip(sum(b - a for a, b in busy) * 1e-9, dict(category_s),
                dict(op_s), dict(op_calls), exposed, hidden, gaps)


def _name_gaps(gaps, modules, host) -> dict:
    """Idle seconds by what was going on: inside a program run the device
    itself left the gap; outside one, the host span that covers most of it
    (the innermost, so never the window's own) names it. Right for gaps of
    milliseconds; the clocks' offset can misname a shorter one."""
    spans = [s for s in host if s.name != WINDOW_SPAN]
    named = collections.defaultdict(float)
    for g0, g1 in gaps:
        if any(m.start <= g0 and g1 <= m.end for m in modules):
            named["inside a program run"] += (g1 - g0) * 1e-9
            continue
        best, best_overlap = "no bench span", 0.0
        for s in spans:
            overlap = min(g1, s.end) - max(g0, s.start)
            if overlap > best_overlap:
                best, best_overlap = s.name, overlap
        named[best] += (g1 - g0) * 1e-9
    return named


def reduce(trace: Trace, ops: dict, steps: int) -> Summary:
    """The per-layer numbers of a traced window of `steps` steps."""
    if not trace.ops:
        raise ValueError("the trace holds no device plane with an "
                         f"{OPS_LINE!r} line: no operation ran on a device")
    # the window is the device's own: from the first operation of the
    # traced steps to the end of the last. The host's clock in a trace runs
    # about a millisecond off the device's (a recorded program starts before
    # the host span that enqueued it), so host spans only name the gaps.
    every = [e for events in trace.ops.values() for e in events]
    window = (min(e.start for e in every), max(e.end for e in every))
    chips = {plane: _reduce_chip(events, ops, window)
             for plane, events in sorted(trace.ops.items())}
    n = len(chips)

    def mean(values):
        return sum(values) / n

    names = sorted({k for c in chips.values() for k in c.op_s})
    op_mean = {k: mean([c.op_s.get(k, 0.0) for c in chips.values()])
               for k in names}
    calls_mean = {k: mean([c.op_calls.get(k, 0) for c in chips.values()])
                  for k in names}
    top = sorted(op_mean.items(), key=lambda kv: -kv[1])[:10]
    worst = max(chips.values(), key=lambda c: c.collective_exposed_s
                + c.collective_hidden_s)
    idlest_plane = min(chips, key=lambda p: chips[p].busy_s)
    gaps = _name_gaps(chips[idlest_plane].gaps,
                      trace.modules.get(idlest_plane, ()), trace.host)
    categories = sorted({k for c in chips.values() for k in c.category_s})
    return Summary(
        steps=steps, chips=n, window_s=(window[1] - window[0]) * 1e-9,
        busy_s=mean([c.busy_s for c in chips.values()]),
        category_s={k: mean([c.category_s.get(k, 0.0)
                             for c in chips.values()]) for k in categories},
        ops=ops, op_s=op_mean, op_calls=calls_mean,
        collective_s=worst.collective_exposed_s + worst.collective_hidden_s,
        collective_exposed_s=worst.collective_exposed_s,
        device_ops=[[f"{k} ({ops[k].label})" if k in ops else k, s]
                    for k, s in top],
        idle_gaps=[[k, s] for k, s in
                   sorted(gaps.items(), key=lambda kv: -kv[1])[:10]])
