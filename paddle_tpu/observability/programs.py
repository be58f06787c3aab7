"""Program building, recorded where it happens: a span for every program
this process traces, lowers, compiles or loads from JAX's persistent cache.

What a job does between its start and its first step is mostly this, and a
stopwatch between calls cannot say which program missed the cache, what a
Pallas call site costs to trace, or how much of a phase was lowering. JAX
tells a listener all of it (`jax.monitoring`: for every function it traces,
lowers or compiles, the name, the start and the end on the epoch clock, and
the persistent cache's hits, misses and retrieval times), and this module
turns that into the package's own records:

- a **span** (`spans.record`) named `trace`, `lower`, `compile` or
  `cache_load`, with JAX's `fun_name`, `start_ns` on the epoch clock (one
  host span stamped on it lays a profiler's trace over the spans:
  `benchmarks/setup_table.py --profile`), its **parent** (the span whose
  interval on the same thread holds it: JAX's listeners fire when a span
  ends, so a span adopts the ended spans of its thread that began after it)
  and a **program id** (`step_fn#1`) that a function's trace, lowering and
  compile or load share. A compile that JAX's cache answered is a
  `cache_load` and carries `retrieval_s` and `saved_s`. **Self time** is a
  span's duration less what its children cover;
- a **`mosaic_site`** span where JAX has no event: `mosaic_site(kernel,
  *operands)` stands around each `pl.pallas_call` the package builds, in
  Python that runs only while a program is traced, and says what one call
  site costs, under which scope of the program (`jax.named_scope`);
- **counters** in the one registry (`metrics`): `programs.traced`,
  `.lowered`, `.compiled`, `.cache_hits`, `.cache_misses`,
  `.traces.<fun_name>` (a function traced twice for one shape reads 2) and a
  histogram of microseconds for each kind. They are counted where the work
  happens and reach the registry when somebody reads (`stats()`, `summary`,
  `rows`, `flush`): a callback takes no lock, and a process in which nobody
  asks does no registry work for them.

Kept in memory and bounded: a model's trace holds thousands of traces of
`jax.numpy`'s small jitted helpers, so a child `trace` under a millisecond
that holds nothing itself is folded into a count and two sums by `fun_name`
under its parent (self times still add up); whole stay the outermost
programs, every lowering, compile, load and Mosaic site, every child over a
millisecond and every function that ever held a Mosaic site.

`register()` is called by `_core.device.enable_compile_cache()` and by
`observability.enable()`; it is idempotent and there is no switch: the cost
is a few Python callbacks for each program BUILT (`callback_s` counts them)
and nothing for one that runs, since no event fires while cached executables
run. `observability.stats()["programs"]` is `summary()`.
"""
from __future__ import annotations

import threading
import time

from . import metrics, spans

TRACE, LOWER, COMPILE, CACHE_LOAD, MOSAIC_SITE = (
    "trace", "lower", "compile", "cache_load", "mosaic_site")
KINDS = (TRACE, LOWER, COMPILE, CACHE_LOAD, MOSAIC_SITE)

_SPAN_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": TRACE,
    "/jax/core/compile/jaxpr_to_mlir_module_duration": LOWER,
    "/jax/core/compile/backend_compile_duration": COMPILE}
_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"
_CACHE_SECONDS = {
    "/jax/compilation_cache/cache_retrieval_time_sec": "retrieval_s",
    "/jax/compilation_cache/compile_time_saved_sec": "saved_s"}
# a load is counted where the cache says so: `programs.cache_hits`
_COUNTER = {TRACE: "programs.traced", LOWER: "programs.lowered",
            COMPILE: "programs.compiled",
            MOSAIC_SITE: "programs.mosaic_sites"}
_HISTOGRAM = {kind: f"programs.{kind}_us" for kind in KINDS}

WHOLE_US = 1000.0       # a child trace under this, holding nothing, is folded
MAX_PENDING = 1 << 15   # what is kept of one kind before the oldest gives way
ROWS = 64               # programs a summary lists, most seconds first


def _base(fun_name: str) -> str:
    """`jit(step_fn)` (a lowering's and a compile's name) -> `step_fn` (its
    trace's)."""
    if fun_name.endswith(")") and "(" in fun_name:
        return fun_name[fun_name.index("(") + 1:-1]
    return fun_name


class _Thread:
    """What one thread has ended and nothing has adopted yet, as (start_ns,
    the span) or, for a leaf (a trace under `WHOLE_US` that held nothing),
    (start_ns, None, microseconds, fun_name); the cache's word on the
    compile it is in; and its functions' open program ids."""
    __slots__ = ("ident", "pending", "cache", "traced", "lowered")

    def __init__(self, ident: int):
        self.ident, self.pending, self.cache = ident, [], None
        self.traced, self.lowered = {}, {}


def _self_by_kind(span, into: dict) -> None:
    """[count, self seconds] of `span` and all under it, by kind."""
    entry = into.setdefault(span.name, [0, 0.0])
    entry[0] += 1
    entry[1] += span.self_us / 1e6
    traces = into.setdefault(TRACE, [0, 0.0])
    for n, _, self_us in span.args.get("folded", {}).values():
        traces[0] += n
        traces[1] += self_us / 1e6
    for child in span.args.get("children", ()):
        _self_by_kind(child, into)


def _open(t: _Thread, base: str, kind: str, open_ids: dict):
    """The program, among `open_ids` ({function: program id} of the spans
    of `kind` whose next step has not come), that a span of `base` goes on
    with: the one of its name; for a name JAX could not give (a jitted
    `functools.partial` is traced under its function's name, then lowered
    and compiled as `<unknown>`), the one whose `kind` the thread ended
    last. None if there is none."""
    if base != "<unknown>" and base in open_ids:
        return open_ids.pop(base)
    last = t.pending[-1][1] if t.pending else None
    if last is not None and last.name == kind:
        name = _base(last.args["fun_name"])
        if open_ids.get(name) == last.args["program"]:
            return open_ids.pop(name)
    return open_ids.pop(base, None)


def _fold(into: dict, fun_name: str, n: int, us: float, self_us: float):
    """{fun_name: [count, microseconds, self microseconds]} of the traces
    that stay no span of their own, under their parent."""
    entry = into.get(fun_name)
    if entry is None:
        into[fun_name] = [n, us, self_us]
    else:
        entry[0] += n
        entry[1] += us
        entry[2] += self_us


class Recorder:
    """The spans of the programs a process built. The three `on_*` are
    JAX's listeners. A model's trace fires thousands of events between
    other work, where every line of a callback runs cold, so a callback
    only writes the event down (`_raw`); `digest` turns what is written
    down into spans (`ended`), at the next lowering, compile or load and
    when somebody asks."""

    def __init__(self):
        self._lock = threading.Lock()
        self.reset()

    def reset(self):
        with self._lock:
            self._raw = []              # (start s, end s, fun_name, thread
            #                             [, kind, args]): a trace has four
            self._threads = {}
            self._ordinals = {}         # function -> programs of it so far
            self._site_holders = set()  # functions that held a Mosaic site
            self._evicted = {}          # dropped for room: `_self_by_kind`
            self._counts = {}           # counter -> what the registry lacks
            self._observed = {}         # histogram -> microseconds it lacks
            self.callbacks = 0
            self.callback_ns = 0

    # ------------------------------------------------------- the way in

    def _thread(self, ident: int) -> _Thread:
        t = self._threads.get(ident)
        if t is None:
            with self._lock:
                t = self._threads.setdefault(ident, _Thread(ident))
        return t

    def _new_program(self, base: str) -> str:
        with self._lock:
            n = self._ordinals[base] = self._ordinals.get(base, 0) + 1
        return f"{base}#{n}"

    def on_event(self, event: str, **_):
        if event == _HIT or event == _MISS:
            t0 = time.perf_counter_ns()
            hit = event == _HIT
            self._thread(threading.get_ident()).cache = {"hit": hit}
            self._count("programs.cache_hits" if hit
                        else "programs.cache_misses")
            self._spent(t0)

    def on_duration(self, event: str, seconds: float, **_):
        key = _CACHE_SECONDS.get(event)
        if key is not None:
            t0 = time.perf_counter_ns()
            found = self._thread(threading.get_ident()).cache
            if found is not None:
                found[key] = seconds
            self._spent(t0)

    def on_time_span(self, event: str, start: float, end: float,
                     fun_name: str = "?", **_):
        kind = _SPAN_EVENTS.get(event)
        if kind is None:
            return
        t0 = time.perf_counter_ns()
        if kind == TRACE:
            raw = self._raw
            raw.append((start, end, fun_name, threading.get_ident()))
            if len(raw) > MAX_PENDING:
                self.digest()
        else:
            self.note(kind, start, end, fun_name)
        self.callbacks += 1
        self.callback_ns += time.perf_counter_ns() - t0

    def note(self, kind: str, start: float, end: float, fun_name: str,
             **args):
        """Write down one span that just ended on this thread (seconds on
        the epoch clock). A compile takes the cache's word with it; it, a
        lowering and a load are a program's boundary, where what was
        written down becomes spans."""
        ident = threading.get_ident()
        if kind == COMPILE:
            t = self._thread(ident)
            found, t.cache = t.cache, None
            if found is not None:       # else: a compile that asked no cache
                if found.pop("hit"):
                    kind = CACHE_LOAD
                    args.update(found)
                args["cache"] = "hit" if kind == CACHE_LOAD else "miss"
        self._raw.append((start, end, fun_name, ident, kind, args))
        if kind != TRACE and kind != MOSAIC_SITE:
            self.digest()

    def _spent(self, t0: int):
        self.callbacks += 1
        self.callback_ns += time.perf_counter_ns() - t0

    def _count(self, name: str, n: int = 1):
        self._counts[name] = self._counts.get(name, 0) + n

    def flush(self):
        """What was counted since the last time, into the registry: a lock
        for each name and one for each histogram."""
        self.digest()
        self._push()

    def _push(self):
        with self._lock:
            counts, self._counts = self._counts, {}
            observed, self._observed = self._observed, {}
        for name, n in counts.items():
            metrics.inc(name, n)
        for name, values in observed.items():
            metrics.histogram(name).observe_all(values)

    def digest(self):
        """What the listeners wrote down, into spans, and the counts of
        the traces among it."""
        raw = self._raw
        n = len(raw)                    # what other threads append stays
        holders, counts, trace_us = self._site_holders, {}, []
        ident = t = None
        for entry in raw[:n]:
            if entry[3] != ident:
                ident = entry[3]
                t = self._thread(ident)
                pending = t.pending
            fun_name = entry[2]
            start_ns = int(entry[0] * 1e9)
            if len(entry) > 4:
                self.ended(entry[4], start_ns, int(entry[1] * 1e9),
                           fun_name, t, **entry[5])
                continue
            counts[fun_name] = counts.get(fun_name, 0) + 1
            dur_us = (entry[1] - entry[0]) * 1e6
            trace_us.append(dur_us)
            if (dur_us < WHOLE_US and fun_name not in holders
                    and not (pending and pending[-1][0] >= start_ns)):
                # a leaf, thousands to a model's trace (every small
                # jitted helper of `jax.numpy`): four words until its
                # parent folds it
                pending.append((start_ns, None, dur_us, fun_name))
            else:
                self.ended(TRACE, start_ns, int(entry[1] * 1e9), fun_name, t)
        del raw[:n]
        if counts:
            self._count(_COUNTER[TRACE], len(trace_us))
            for fun_name, n in counts.items():
                self._count("programs.traces." + fun_name, n)
            held = self._observed.setdefault(_HISTOGRAM[TRACE], [])
            held.extend(trace_us)
            if len(held) > MAX_PENDING:     # nobody reads: bounded all the same
                self._push()

    def ended(self, kind: str, start_ns: int, end_ns: int, fun_name: str,
              thread: _Thread = None, **args):
        """The span of `kind` that ran from `start_ns` to `end_ns` (epoch)
        on `thread` (the calling one), given in the order spans end: it
        adopts what its thread ended inside it."""
        t = thread or self._thread(threading.get_ident())
        s = spans.record(kind, start_ns, (end_ns - start_ns) / 1e3,
                         fun_name=fun_name, program=None, thread=t.ident,
                         **args)
        if kind != TRACE:               # the traces' counts: `digest`
            if kind in _COUNTER:
                self._count(_COUNTER[kind])
            self._observed.setdefault(_HISTOGRAM[kind], []).append(s.dur_us)

        pending, whole, folded = t.pending, [], None
        while pending and pending[-1][0] >= start_ns:
            entry = pending.pop()
            child = entry[1]
            if child is None:           # a leaf: its self time is all of it
                if folded is None:
                    folded = s.args.setdefault("folded", {})
                s.children_us += entry[2]
                _fold(folded, entry[3], 1, entry[2], entry[2])
                continue
            if child.name == MOSAIC_SITE and kind == TRACE:
                self._site_holders.add(fun_name)
            if (child.name != TRACE or child.dur_us >= WHOLE_US
                    or "children" in child.args
                    or child.args["fun_name"] in self._site_holders):
                s.adopt(child)
                whole.append(child)
                continue
            if folded is None:
                folded = s.args.setdefault("folded", {})
            s.children_us += child.dur_us
            _fold(folded, child.args["fun_name"], 1, child.dur_us,
                  child.self_us)
            for name, entry in child.args.get("folded", {}).items():
                _fold(folded, name, *entry)
        if whole:
            whole.reverse()             # in the order they ran
            s.args["children"] = whole
        # its program: after the adoption, so that the span the thread
        # ended last is the one BEFORE this one and not one inside it
        base = _base(fun_name)
        if kind == TRACE:
            s.args["program"] = t.traced[base] = self._new_program(base)
        elif kind == LOWER:
            s.args["program"] = t.lowered[base] = (
                _open(t, base, TRACE, t.traced) or self._new_program(base))
        elif kind != MOSAIC_SITE:       # a site's is its trace's: `within`
            s.args["program"] = (_open(t, base, LOWER, t.lowered)
                                 or _open(t, base, TRACE, t.traced)
                                 or self._new_program(base))
        pending.append((start_ns, s))
        if len(pending) > MAX_PENDING:  # a long job's outermost programs
            with self._lock:
                for old in pending[:MAX_PENDING // 2]:
                    _self_by_kind(_whole(old, t.ident), self._evicted)
            del pending[:MAX_PENDING // 2]
        return s

    # ------------------------------------------------------ the way out

    def roots(self) -> list:
        """The spans nothing adopted, oldest first: the outermost programs
        (and, while a trace is under way, its children so far). Brings the
        registry up to date on the way."""
        self.flush()
        with self._lock:
            threads = list(self._threads.values())
        return sorted((_whole(entry, t.ident) for t in threads
                       for entry in list(t.pending)),
                      key=lambda s: s.start_ns)

    def rows(self) -> list:
        """Every whole span as a flat row, parents before children: `id`,
        `parent` (an id or None), `kind`, `fun_name`, `program` (its own;
        a Mosaic site has none), `within` (the outermost program it was
        part of), `thread`, `start_ns`, `end_ns`, `s` and `self_s`,
        `folded` and `folded_self_s` (the traces folded into it and their
        self seconds), and what else the span carries (`cache`,
        `retrieval_s`, `saved_s`; a site's `shapes` and `scope`)."""
        out = []

        def walk(s, parent, within):
            args = s.args
            within = within or args["program"]
            folded = args.get("folded", {})
            row = {"id": len(out), "parent": parent, "kind": s.name,
                   "fun_name": args["fun_name"], "program": args["program"],
                   "within": within,
                   "start_ns": s.start_ns, "end_ns": s.end_ns,
                   "s": s.dur_us / 1e6, "self_s": s.self_us / 1e6,
                   "folded": sum(e[0] for e in folded.values()),
                   "folded_self_s": sum(e[2] for e in folded.values()) / 1e6}
            row.update((k, v) for k, v in args.items() if k not in (
                "fun_name", "program", "folded", "children"))
            out.append(row)
            for child in args.get("children", ()):
                walk(child, row["id"], within)

        for root in self.roots():
            walk(root, None, None)
        return out

    def summary(self) -> dict:
        """`stats()["programs"]`: the totals (self seconds by kind, so they
        add up to the time spent building programs), a row for each
        outermost program (trace self / trace of children / lower /
        compile or load, hit or miss; the `ROWS` that cost most), the
        functions traced more often than their Mosaic sites have distinct
        shapes, and what the recorder's own callbacks cost."""
        rows = self.rows()
        self_s = dict.fromkeys(KINDS, 0.0)
        count = dict.fromkeys(KINDS, 0)
        programs, built, site_s = {}, {}, 0.0
        by_id = {}
        for row in rows:
            by_id[row["id"]] = row
            kind = row["kind"]
            self_s[kind] += row["self_s"]
            self_s[TRACE] += row["folded_self_s"]
            count[kind] += 1
            count[TRACE] += row["folded"]
            if kind == MOSAIC_SITE:
                site_s += row["s"]
                holder = by_id.get(row["parent"])
                if holder is not None and holder["kind"] == TRACE:
                    built.setdefault(holder["fun_name"], {}).setdefault(
                        holder["id"], []).append(
                            (row["fun_name"], str(row.get("shapes"))))
            if row["within"] is None:   # a site called eagerly, under no trace
                continue
            if kind != MOSAIC_SITE and row["parent"] is not None:
                continue                # inside its outermost program's trace
            p = programs.setdefault(row["within"], {
                "program": row["within"], "fun_name": _base(row["fun_name"]),
                "start_ns": row["start_ns"], "trace_self_s": 0.0,
                "trace_children_s": 0.0, "lower_s": 0.0, "compile_s": 0.0,
                "cache_load_s": 0.0, "cache": None, "mosaic_sites": 0})
            if kind == MOSAIC_SITE:
                p["mosaic_sites"] += 1
            elif kind == TRACE:
                p["trace_self_s"] += row["self_s"]
                p["trace_children_s"] += row["s"] - row["self_s"]
            else:
                p[kind + "_s"] += row["s"]
                if "cache" in row:
                    p["cache"] = row["cache"]
                    p["retrieval_s"] = row.get("retrieval_s")
                    p["saved_s"] = row.get("saved_s")
        for kind, (n, seconds) in self._evicted.items():
            count[kind] += n            # dropped for room: the sums stay
            self_s[kind] += seconds
        outermost = list(programs.values())
        for p in outermost:
            p["s"] = (p["trace_self_s"] + p["trace_children_s"] + p["lower_s"]
                      + p["compile_s"] + p["cache_load_s"])
        outermost.sort(key=lambda p: -p["s"])
        retraced = {}
        for fun_name, traces in built.items():
            distinct = {tuple(sorted(sites)) for sites in traces.values()}
            retraced[fun_name] = {
                "traces": metrics.counter(
                    "programs.traces." + fun_name).value,
                "built": len(traces), "distinct": len(distinct)}
        return {
            "totals": {
                "trace_s": self_s[TRACE], "lower_s": self_s[LOWER],
                "compile_s": self_s[COMPILE],
                "cache_load_s": self_s[CACHE_LOAD],
                "mosaic_site_s": self_s[MOSAIC_SITE],
                "mosaic_site_whole_s": site_s,  # with the traces inside
                "traced": count[TRACE], "lowered": count[LOWER],
                "compiled": count[COMPILE], "cache_loads": count[CACHE_LOAD],
                "mosaic_sites": count[MOSAIC_SITE],
                # built, and not only traced for its shapes
                "programs": sum(1 for p in outermost if p["s"]
                                > p["trace_self_s"] + p["trace_children_s"]),
                "retraced_functions": sum(
                    1 for r in retraced.values()
                    if r["built"] > r["distinct"])},
            "programs": outermost[:ROWS],
            "programs_not_listed": max(0, len(outermost) - ROWS),
            "retraced": retraced,
            "callbacks": self.callbacks,
            "callback_s": self.callback_ns / 1e9}


def _whole(entry, ident: int) -> spans.Span:
    """A pending entry's span; a leaf that nothing adopted gets one now."""
    if entry[1] is not None:
        return entry[1]
    s = spans.Span(TRACE, args={"fun_name": entry[3], "program": None,
                                "thread": ident})
    s.start_ns, s.dur_us = entry[0], entry[2]
    return s


RECORDER = Recorder()
_REGISTERED = False


def register() -> None:
    """Hand JAX the process's recorder, once."""
    global _REGISTERED
    with RECORDER._lock:
        if _REGISTERED:
            return
        _REGISTERED = True
    from jax import monitoring
    monitoring.register_event_listener(RECORDER.on_event)
    monitoring.register_event_duration_secs_listener(RECORDER.on_duration)
    monitoring.register_event_time_span_listener(RECORDER.on_time_span)


def registered() -> bool:
    return _REGISTERED


def summary() -> dict:
    return RECORDER.summary()


def rows() -> list:
    return RECORDER.rows()


def reset() -> None:
    RECORDER.reset()


def flush() -> None:
    """Bring the registry's `programs.*` up to date, for one who reads it
    and not `stats()`."""
    RECORDER.flush()


def _kernel_name(kernel) -> str:
    return getattr(getattr(kernel, "func", kernel), "__name__", str(kernel))


class mosaic_site:
    """Stands around the build and the call of one `pl.pallas_call`:

        with mosaic_site(kernel, q, k, v):
            return pl.pallas_call(kernel, ...)(q, k, v)

    The Python inside runs while a program is traced (or, called eagerly,
    while the kernel's own program is built), so the span is what the call
    site costs there, with the kernel body's name, its operands' shapes and
    the scopes the program had open. It stamps a clock and touches no
    tracer: nothing of it reaches the jaxpr. Nothing at all happens before
    `register()`."""
    __slots__ = ("kernel", "operands", "_start")

    def __init__(self, kernel, *operands):
        self.kernel, self.operands, self._start = kernel, operands, None

    def __enter__(self):
        if _REGISTERED:
            self._start = time.time()
        return self

    def __exit__(self, et, ev, tb):
        if self._start is not None and et is None:
            end = time.time()
            t0 = time.perf_counter_ns()
            from jax.extend import source_info_util
            RECORDER.note(
                MOSAIC_SITE, self._start, end, _kernel_name(self.kernel),
                shapes=[f"{a.dtype}{list(a.shape)}" for a in self.operands],
                scope=str(source_info_util.current_name_stack()))
            RECORDER._spent(t0)
        return False


def render(summary: dict, top: int = 10) -> str:
    """The table `python -m paddle_tpu.observability` prints: one row a
    program, seconds, most first, then the totals."""
    totals = summary["totals"]
    lines = ["== programs built (s; trace self / trace of children / lower / "
             "compile / cache load) =="]
    for p in summary["programs"][:top]:
        lines.append(
            f"  {p['program']:<36} {p['trace_self_s']:8.3f} "
            f"{p['trace_children_s']:8.3f} {p['lower_s']:8.3f} "
            f"{p['compile_s']:8.3f} {p['cache_load_s']:8.3f}  "
            f"{p['cache'] or '-':<4} sites {p['mosaic_sites']}")
    rest = len(summary["programs"]) - top + summary["programs_not_listed"]
    if rest > 0:
        lines.append(f"  ... and {rest} more")
    lines.append(
        f"  totals: trace {totals['trace_s']:.3f} ({totals['traced']}), "
        f"lower {totals['lower_s']:.3f} ({totals['lowered']}), compile "
        f"{totals['compile_s']:.3f} ({totals['compiled']}), cache load "
        f"{totals['cache_load_s']:.3f} ({totals['cache_loads']}), Mosaic "
        f"sites {totals['mosaic_site_s']:.3f} ({totals['mosaic_sites']}); "
        f"{totals['programs']} programs built, "
        f"{totals['retraced_functions']} functions retraced for one shape")
    lines.append(f"  the recorder's own callbacks: {summary['callbacks']} in "
                 f"{summary['callback_s'] * 1e3:.2f} ms")
    return "\n".join(lines)
