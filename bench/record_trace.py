#!/usr/bin/env python3
"""Record a few engine steps of a serving cell's device trace, as the small
trace that ``tests/bench`` checks the trace reduction on.

    python bench/record_trace.py --workload danube3.decode_heavy --seed 7 \
        --seconds 2 --steps 4 --out trace.json.gz

Writes the device operations and host spans that fall within ``--steps``
consecutive window steps, and each of those steps' record.
"""
from __future__ import annotations

import argparse
import dataclasses
import gzip
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, default=2.0)
    p.add_argument("--steps", type=int, default=4)
    p.add_argument("--out", required=True)
    args = p.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench.harness import device, serve, spec
    from bench.run import enable_compile_cache

    cell = spec.load_cell(args.workload, ROOT)
    devices = device.require_chips(cell.chips)
    enable_compile_cache(ROOT)
    state_dir = ROOT / ".bench_state"
    state_dir.mkdir(exist_ok=True)
    rec, _ = serve.run(cell, args.seed, args.seconds, True, devices,
                       time.perf_counter(), state_dir, print)
    ev = rec.events
    spans = sorted((t0, d) for n, t0, d, _ in ev.host if n == "bench.step")
    (w0, wd), = [(t0, d) for n, t0, d, _ in ev.host if n == "bench.window"]
    spans = [s for s in spans if w0 <= s[0] and s[0] + s[1] <= w0 + wd]
    mid = len(spans) // 2
    pick = spans[mid:mid + args.steps]
    lo, hi = pick[0][0], pick[-1][0] + pick[-1][1]
    keep = {
        "device": {k: [e for e in v if e[1] < hi and e[1] + e[2] > lo]
                   for k, v in ev.device.items()},
        "host": [e for e in ev.host if e[1] < hi and e[1] + e[2] > lo
                 and (e[0].startswith("bench.") or e[2] > 1e5)],
    }
    # window steps and the window's bench.step spans match one to one
    steps = serve.window_steps(rec)[mid:mid + args.steps]
    out = {"cell": cell.name, "device_kind": devices[0].device_kind,
           "window_ns": [lo, hi], "events": keep,
           "steps": [dataclasses.asdict(s) for s in steps]}
    with gzip.open(args.out, "wt") as f:
        json.dump(out, f)
    print(f"wrote {args.out}: {sum(len(v) for v in keep['device'].values())} "
          f"device events, {len(keep['host'])} host spans")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
