#!/usr/bin/env python3
"""Record the small real trace that tests/benchmark/test_bench_stages.py
reads: two steps of the real `paddle_tpu.models.gpt.build_train_step` at a
tiny size (two layers, flash kernels, whole-block remat, AdamW) on one TPU
chip, through the harness's own loop and spans.

    chiprun --chips 1 -- python3 benchmarks/testdata/record_stages_trace.py

writes `chiprun_out/recorded_stages_1chip.textproto.gz` and the compiled
step's HLO text, gzipped, beside it; copy both into benchmarks/testdata/ to
replace the fixture. Every instruction's `op_name` there carries the stage
the program gave it (`paddle_tpu/models/stages.py`). The real step's
`.xplane.pb` is a megabyte, nearly all of it statistics and planes the
reduction never reads, so what is kept is what `trace_reduce.from_profile`
reads and nothing else (`slim`), in the text form of the hand-made traces.
"""
from __future__ import annotations

import gzip
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

LAYERS, HIDDEN, HEADS, VOCAB, BATCH, SEQ = 2, 256, 4, 512, 8, 256
STEPS, SYNC_EVERY = 2, 1
NAME = "recorded_stages_1chip"


def slim(profile) -> str:
    """An XSpace in text form with what `trace_reduce.from_profile` reads of
    `profile` (a `ProfileData`): the device planes' `XLA Ops` and `XLA
    Modules` lines and the host's `bench/` spans, each event by name, start
    and duration in whole picoseconds."""
    from benchmarks import trace_reduce as tr
    out = []
    for plane in profile.planes:
        device = tr.DEVICE_PLANE.match(plane.name)
        if not (device or plane.name == tr.HOST_PLANE):
            continue
        ids, lines = {}, []
        for line in plane.lines:
            events = [e for e in line.events
                      if (line.name in (tr.OPS_LINE, tr.MODULES_LINE)
                          if device else e.name.startswith(tr.SPAN_PREFIX))]
            if not events:
                continue
            lines.append(f'  lines {{\n    name: "{line.name}"\n' + "".join(
                f"    events {{ metadata_id: "
                f"{ids.setdefault(e.name, len(ids) + 1)} offset_ps: "
                f"{round(e.start_ns * 1000)} duration_ps: "
                f"{round(e.duration_ns * 1000)} }}\n" for e in events)
                + "  }\n")
        names = "".join(
            f"  event_metadata {{ key: {i} value {{ id: {i} name: "
            f"{json.dumps(name)} }} }}\n" for name, i in ids.items())
        out.append(f'planes {{\n  name: "{plane.name}"\n'
                   + "".join(lines) + names + "}\n")
    return "".join(out)


def main() -> int:
    import jax
    import numpy as np
    from jax.profiler import ProfileData

    from benchmarks import run
    from benchmarks.runners import Program
    from paddle_tpu.models import gpt
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("record_stages_trace.py: no TPU", file=sys.stderr)
        return 2
    init_fn, jitted = gpt.build_train_step(gpt.GPTConfig(
        vocab_size=VOCAB, hidden_size=HIDDEN, num_layers=LAYERS,
        num_heads=HEADS, max_position_embeddings=SEQ, dtype="bfloat16"))
    state = jax.jit(init_fn)(0)
    rng = np.random.default_rng(0)
    ring = [tuple(rng.integers(0, VOCAB, (BATCH, SEQ), dtype=np.int32)
                  for _ in range(2)) for _ in range(2)]
    step = jitted.lower(state, *ring[0]).compile()
    state, _ = step(state, *ring[0])        # warm

    program = Program(
        step=step, state=state, ring=ring, put=lambda batch: batch,
        unit="tokens", units_per_step=BATCH * SEQ, flops_per_unit=0.0,
        problems=[])
    (steps, _, losses), xplane = run.traced_window(
        NAME, {"sync_every": SYNC_EVERY, "trace_steps": STEPS}, program)
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with gzip.open(os.path.join(out, f"{NAME}.textproto.gz"), "wt") as f:
        f.write(slim(ProfileData.from_file(xplane)))
    with gzip.open(os.path.join(out, f"{NAME}.hlo.txt.gz"), "wt") as f:
        f.write(step.as_text())
    print(f"{steps} steps, losses {losses}, {os.path.getsize(xplane)} bytes "
          "of trace from one chip")
    return 0


if __name__ == "__main__":
    sys.exit(main())
