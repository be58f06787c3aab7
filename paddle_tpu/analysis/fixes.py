"""Autofix: mechanical repair of sanitizer findings, then re-check.

`FLAGS_static_checks=fix` (and `python -m paddle_tpu.analysis --fix`)
turns the sanitizer from a reporter into a rewriter for the finding
classes whose repair is purely mechanical — the fix is exactly what
the diagnostic's hint tells a human to do, applied to the segment
about to flush:

- **unsafe donation** (`donation_safety` / `view_alias` donation
  findings): drop the offending index from the donation mask. The
  segment runs correctly with one more copy instead of reading freed
  memory.
- **missing note_inplace** (`inplace_race`): perform the notification
  the mutation site skipped — evict the tensor's input registration
  from the capture context so future records re-register the fresh
  payload (ops already recorded keep the snapshot, eager ordering).
  KNOWN BOUNDARY of post-hoc repair: a real note_inplace at the
  mutation point would ALSO have made records between the mutation and
  the flush re-register the fresh payload; applying it at flush time
  cannot rewire those retroactively (the record timestamps are gone),
  so they keep their recorded stale-snapshot semantics — the same ops
  error mode can only drop wholesale. The repair is exact for the
  common class (mutation after the last read) and forward-correct for
  all future records.
- **dead captures** (`dead_capture`): prune the unobservable ops from
  the pending list, remapping downstream wiring / LazyRef indices /
  the incremental signature, so the compiled program never contains
  them.
- **leaked tracers** (`tracer_leak`): a tracer that outlived its trace
  is unexecutable by definition — every flush of the poisoned program
  dies with UnexpectedTracerError. The mechanical eviction: pop
  tracer entries from the process scalar-coercion cache
  (`executor._SCALAR_CACHE`), and for a tracer segment input (or an
  op whose attrs closed over one) prune the poisoned forward closure
  and swap the input slot to a concrete placeholder — but ONLY when
  no live tensor aliases a poisoned output (then the user would
  observe the substitution, so the finding stays reported like warn).

Non-mechanical classes (shape drift, cross-segment donation, guard
contradictions, distributed findings) are NOT touched: their repair
needs intent the checker cannot infer, so fix mode reports them
exactly like warn mode.

Every applied fix bumps `sanitizer.fixes_applied` (tests/test_analysis.py
asserts the counter stays FROZEN over a clean program — fix mode must
never rewrite correct code) and notes a flight-recorder event. After
applying, the caller re-runs the checkers to prove the diagnostic
clears; `FixResult.diff()` renders the before/after segment for the
CLI's dry-run printout.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

from .diagnostics import CheckReport

# checkers fixes.py knows how to repair
FIXABLE = ("donation_safety", "view_alias", "inplace_race",
           "dead_capture", "tracer_leak", "numerics.cast_churn")


def _poison_closure(view, roots):
    """Every op reachable forward from `roots` through the segment
    dataflow — the set a leaked tracer poisons."""
    closure = set(roots)
    changed = True
    while changed:
        changed = False
        for j, p in enumerate(view.pending):
            if j in closure:
                continue
            for w in p.wiring:
                if w is not None and w[0] != "in" and w[1] in closure:
                    closure.add(j)
                    changed = True
                    break
    return closure


class FixResult:
    __slots__ = ("pending", "donate", "actions", "before_ops",
                 "after_ops", "before_donate", "consumed")

    def __init__(self, pending, donate, actions, before_ops, after_ops,
                 before_donate, consumed=()):
        self.pending = pending
        self.donate = donate
        self.actions = actions          # human-readable, one per fix
        self.before_ops = before_ops
        self.after_ops = after_ops
        self.before_donate = before_donate
        self.consumed = list(consumed)  # diagnostics a fix addresses

    @property
    def n_applied(self) -> int:
        return len(self.actions)

    def diff(self) -> str:
        """Unified-ish dry-run printout: what fix mode rewrites."""
        lines = [f"fix plan: {self.n_applied} rewrite(s)"]
        for a in self.actions:
            lines.append(f"  * {a}")
        if any(not alive for _, alive in self.before_ops):
            for j, (name, alive) in enumerate(self.before_ops):
                mark = " " if alive else "-"
                lines.append(f"  {mark} op #{j} {name}")
        if tuple(self.before_donate) != tuple(self.donate):
            lines.append(f"  - donate_argnums {tuple(self.before_donate)}")
            lines.append(f"  + donate_argnums {tuple(self.donate)}")
        return "\n".join(lines)


def plan_and_apply(view, report: CheckReport, ctx=None,
                   dry_run: bool = False) -> FixResult:
    """Repair the mechanical findings of `report` against `view` (and
    the live CaptureContext when given). Returns the FixResult with the
    rewritten (pending, donate); with `dry_run` nothing is mutated and
    no counters move — the CLI's diff-printout mode."""
    if ctx is None:
        # a view snapshot knows its source context: repairs proven on
        # the view must land on the real program too
        ctx = getattr(view, "ctx", None)
    actions: List[str] = []
    consumed = []
    donate = list(view.donate)
    drop: set = set()
    evict_inputs: set = set()
    dead_ops: List[int] = []
    scalar_keys: List = []
    tracer_inputs: set = set()
    cast_rewires: List[Tuple[int, Tuple]] = []   # (j2, source wiring)

    for d in report.diagnostics:
        if d.checker not in FIXABLE:
            continue
        data = d.data or {}
        if d.checker in ("donation_safety", "view_alias"):
            di = data.get("donate_index")
            if di is None:
                continue
            consumed.append(d)
            for i in (di if isinstance(di, list) else [di]):
                if i not in drop:
                    drop.add(i)
                    actions.append(
                        f"drop donation of input {i} "
                        f"({d.checker}: {d.message.split(':')[0]})")
        elif d.checker == "inplace_race":
            i = data.get("input")
            if i is not None:
                consumed.append(d)
                if i not in evict_inputs:
                    evict_inputs.add(i)
                    actions.append(
                        f"insert missing note_inplace for input {i} "
                        f"(evict its capture registration)")
        elif d.checker == "dead_capture":
            if data.get("dead_ops"):
                consumed.append(d)
                for j in data["dead_ops"]:
                    if j not in dead_ops:
                        dead_ops.append(j)
                names = [view.pending[j].op.name
                         for j in data["dead_ops"][:4]]
                actions.append(
                    f"prune {len(data['dead_ops'])} dead op(s) "
                    f"{names} (~{data.get('flops', 0)} FLOPs)")
        elif d.checker == "numerics.cast_churn":
            pair = data.get("cast_pair")
            src = data.get("source")
            # an aliased round-trip output would make the substitution
            # observable (the alias's ref points at the pruned op) —
            # report-only, the residual re-check warns it
            if not pair or src is None or not data.get("fixable"):
                continue
            consumed.append(d)
            j1, j2 = pair
            cast_rewires.append((j2, tuple(src)))
            for j in (j1, j2):
                if j not in dead_ops:
                    dead_ops.append(j)
            actions.append(
                f"drop redundant cast round trip (ops #{j1}, #{j2}): "
                f"rewire consumers to the original value")
        elif d.checker == "tracer_leak":
            if "scalar_key" in data:
                consumed.append(d)
                scalar_keys.append(data["scalar_key"])
                actions.append(
                    f"evict leaked tracer from the scalar-coercion "
                    f"cache (key {data['scalar_key']!r})")
            elif "tracer_input" in data or "tracer_op" in data:
                if "tracer_input" in data:
                    i = data["tracer_input"]
                    closure = _poison_closure(
                        view, view.readers_of_input(i))
                else:
                    i = None
                    closure = _poison_closure(view, [data["tracer_op"]])
                if any(j in closure for j, _s in view.live):
                    # a live tensor aliases a poisoned output: the
                    # substitution would be observable — not mechanical
                    continue
                consumed.append(d)
                for j in sorted(closure):
                    if j not in dead_ops:
                        dead_ops.append(j)
                if i is not None:
                    tracer_inputs.add(i)
                    if i not in drop:
                        drop.add(i)   # never donate a placeholder slot
                actions.append(
                    "evict leaked tracer "
                    + (f"input {i}" if i is not None
                       else f"attrs of op #{data['tracer_op']}")
                    + f": prune its {len(closure)} poisoned op(s)"
                    + (" and swap the slot to a concrete placeholder"
                       if i is not None else ""))

    before_donate = tuple(donate)
    before_ops = [(p.op.name, True) for p in view.pending]
    new_pending = view.pending
    new_donate = tuple(i for i in donate if i not in drop)

    if dry_run:
        for j in dead_ops:
            before_ops[j] = (before_ops[j][0], False)
        return FixResult(new_pending, new_donate, actions, before_ops,
                         [n for n, alive in before_ops if alive],
                         before_donate, consumed)

    # ---- apply: note_inplace insertion
    for i in sorted(evict_inputs):
        t = view.in_tensors[i] if i < len(view.in_tensors) else None
        if t is None:
            continue
        view.in_ids.pop(id(t), None)
        if ctx is not None:
            ctx.note_inplace(t)

    # ---- apply: cast-churn consumer rewiring. MUST precede the prune:
    # _prune_dead re-reads every surviving op's wiring (for both the
    # remap and the rebuilt cache signature), so consumers pointing at
    # the doomed cast have to point at the original value first.
    for j2, src in cast_rewires:
        for p in view.pending:
            p.wiring = tuple(
                src if (w is not None and w[0] == "op"
                        and w[1] == j2 and w[2] == 0) else w
                for w in p.wiring)

    # ---- apply: dead-capture pruning (wiring/sig/ref remap)
    if dead_ops:
        new_pending = _prune_dead(view, ctx, sorted(dead_ops))
        for j in sorted(dead_ops):
            before_ops[j] = (before_ops[j][0], False)

    # ---- apply: leaked-tracer evictions
    if scalar_keys:
        from .._core import executor
        for key in scalar_keys:
            executor._SCALAR_CACHE.pop(key, None)
            # the shared Tensor wrapper mirrors the array cache entry
            # (it wraps the same payload) — evict both in lockstep
            executor._SCALAR_TENSORS.pop(key, None)
    if tracer_inputs:
        # after the poisoned closure is pruned nothing reads these
        # slots; a concrete placeholder of the same aval keeps the
        # input indexing intact without closing over the dead trace
        import jax.numpy as jnp
        for i in sorted(tracer_inputs):
            v = view.in_vals[i]
            aval = getattr(v, "aval", None)
            ph = jnp.zeros(aval.shape, aval.dtype) \
                if aval is not None else jnp.zeros(())
            view.in_vals[i] = ph
            if ctx is not None and i < len(ctx._in_vals) \
                    and ctx._in_vals is not view.in_vals:
                ctx._in_vals[i] = ph
            t = view.in_tensors[i] if i < len(view.in_tensors) else None
            if t is not None:
                view.in_ids.pop(id(t), None)
                if ctx is not None:
                    ctx.note_inplace(t)

    # ---- apply: donation drops (already computed)
    view.donate = new_donate

    if actions:
        from ..observability import _state as _obs
        from ..observability import metrics
        metrics.inc("sanitizer.fixes_applied", len(actions))
        if _obs.FLIGHT:
            from ..observability import flight
            for a in actions:
                flight.note("sanfix", "rewrite", action=a[:160])
    return FixResult(new_pending, new_donate, actions, before_ops,
                     [n for n, alive in before_ops if alive],
                     before_donate, consumed)


def _prune_dead(view, ctx, dead: List[int]):
    """Remove `dead` op indices from the pending list, remapping the
    wiring of surviving ops, their LazyRef op indices, the live-output
    index pairs, and the context's incremental signature."""
    dead_set = set(dead)
    idx_map = {}
    new_pending = []
    for j, p in enumerate(view.pending):
        if j in dead_set:
            continue
        idx_map[j] = len(new_pending)
        new_pending.append(p)
    for p in new_pending:
        p.wiring = tuple(
            w if w is None or w[0] == "in"
            else (w[0], idx_map[w[1]], w[2])
            for w in p.wiring)
        for ref in p.out_refs:
            if getattr(ref, "op_idx", None) is not None:
                ref.op_idx = idx_map.get(ref.op_idx, ref.op_idx)
    view.pending = new_pending
    view.live = [(idx_map[j], s) for (j, s) in view.live
                 if j in idx_map]
    if ctx is not None:
        ctx.pending = new_pending
        # surviving _sig_ops entries in order; the akey/n_outs halves
        # are index-independent, the wiring half is re-read from the
        # remapped _PendingOp so the cache signature stays truthful
        old_sigs = [ctx._sig_ops[j] for j in sorted(idx_map)]
        ctx._sig_ops = [
            (name, akey, p.wiring, n_outs)
            for (name, akey, _w, n_outs), p in zip(old_sigs, new_pending)]
    return new_pending
