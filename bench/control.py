#!/usr/bin/env python3
"""The readings that a cell's limits are set from, on the chip, in one
process: for each seed, one run of the cell as ``run.py`` makes it and the
program's numbers; for the first ``--control`` seeds also the control's
numbers, and for a training cell the numbers of a planted fault.

    python bench/control.py --workload <name> --seconds <s> --seeds 11 12 13 \
        --control 3

The control is the reference put in the program's place and computed in
float8 (e4m3), the precision below the configuration's bfloat16.  In a
serving cell it reads, at each position of the sampled prompts and served
tokens, the gap under the float32 reference of the token that float8 puts
first.  In a training cell it is compared with the float32 reference as the
program is; the planted fault is the reference taking the mean over half of
the batch, which is also what a step that leaves out the gradient exchange
between the two data-parallel halves computes on each half.  Prints one JSON
line per seed.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def readings(cell, seeds, seconds, devices, root: Path, n_control=None,
             log=print):
    """Yield one dict of readings per seed."""
    from bench.harness import check, serve, train

    state_dir = root / ".bench_state"
    state_dir.mkdir(exist_ok=True)
    n_control = len(seeds) if n_control is None else n_control
    for i, seed in enumerate(seeds):
        if cell.traffic["kind"] == "train":
            _, st = train.run(cell, seed, seconds, False, devices,
                              time.perf_counter(), state_dir, log)
            args = (cell.config, st["opt"], st["key"], st["batches"], devices)
            ref = train.reference(*args)
            out = {"seed": seed, "program": train.compare(st, ref)}
            if i < n_control:
                out["control"] = train.compare(
                    train.reference(*args, mode="fp8"), ref)
                half = slice(0, len(st["batches"][0]["tokens"]) // 2)
                out["half_batch"] = train.compare(
                    train.reference(*args, rows=half), ref)
        else:
            _, st = serve.run(cell, seed, seconds, False, devices,
                              time.perf_counter(), state_dir, log)
            args = (cell.config, st["params"], st["requests"],
                    cell.traffic["engine"]["max_len"])
            out = {"seed": seed,
                   "program": {"max_logit_gap": check.widest_gap(*args)}}
            if i < n_control:
                out["control"] = {"max_logit_gap": check.control_gap(*args)}
        del st, args
        gc.collect()
        out["bytes_in_use"] = [(d.memory_stats() or {}).get("bytes_in_use")
                               for d in devices]
        yield out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--control", type=int, default=None,
                   help="read the control on the first this many seeds")
    args = p.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench.harness import device, spec
    from bench.run import enable_compile_cache

    cell = spec.load_cell(args.workload, ROOT)
    try:
        devices = device.require_chips(cell.chips)
    except device.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    enable_compile_cache(ROOT)
    for out in readings(cell, args.seeds, args.seconds, devices, ROOT,
                        args.control):
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
