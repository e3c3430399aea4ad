#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix, metrics and limits are found by
name from ``BENCHMARK.json`` at the checkout root (see ``harness/spec.py``).
With ``--trace 0`` the result reports the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a device trace of the window.

The run needs a TPU with as many chips as the cell asks for; without one it
exits non-zero and prints no result.  Its last line of standard output is
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` (and with ``--trace 1`` ``breakdown``), then ``checks``, each
number compared beside its limit.  The same numbers end standard error.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path


def _process_age() -> float:
    """Seconds since this process started, from the kernel's record."""
    try:
        with open("/proc/self/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return max(0.0, up - int(fields[19]) / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return 0.0


T_START = time.perf_counter() - _process_age()
ROOT = Path(__file__).resolve().parents[1]


def _log(msg: str) -> None:
    print(msg, flush=True)


def reader(name: str, root: Path):
    """The ``read`` function of ``bench/metrics/<name>.py``."""
    from bench.harness.spec import import_file

    return import_file(root / "bench" / "metrics" / f"{name}.py",
                       f"bench_metric_{name}").read


def enable_compile_cache(root: Path) -> str:
    """JAX's persistent compilation cache, at a fixed path in the checkout;
    every program is kept, however quickly it compiled."""
    import jax

    path = str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def main(argv: list[str] | None = None, *, root: Path = ROOT,
         require_chips=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    sys.path[:0] = [str(root), str(root / "src")]
    from bench.harness import check, device, spec

    cell = spec.load_cell(args.workload, root)
    try:
        devices = (require_chips or device.require_chips)(cell.chips)
    except device.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    kind = devices[0].device_kind
    peaks = device.Peaks.of(kind) if require_chips is None else None
    _log(f"bench: {cell.name} seed {args.seed} on {len(devices)} x {kind}; "
         f"compile cache {enable_compile_cache(root)}")
    state_dir = root / ".bench_state"
    state_dir.mkdir(exist_ok=True)

    traced = bool(args.trace)
    if cell.traffic["kind"] == "serve_closed_loop":
        from bench.harness import serve as runner
    elif cell.traffic["kind"] == "train":
        from bench.harness import train as runner
    else:
        raise ValueError(f"unknown traffic kind {cell.traffic['kind']!r}")
    rec, state = runner.run(cell, args.seed, args.seconds, traced, devices,
                            T_START, state_dir, _log)
    mem = device.memory_peak(devices)
    attempted, failed = runner.counts(rec)

    metrics = {}
    for mt in (cell.per_layer if traced else cell.end_to_end):
        value = reader(mt["name"], root)(rec, peaks)
        if value is not None:
            metrics[mt["name"]] = {"value": value, "unit": mt["unit"]}
    t_check = time.perf_counter()
    readings = runner.readings(cell, state)
    correct, compared = check.judge(readings, cell.limits["limits"])
    _log(f"check: reference took {time.perf_counter() - t_check:.3f} s")

    dev = device.describe(devices)
    dev["memory_peak_bytes"] = mem
    out = {"correct": correct and failed == 0, "attempted": attempted,
           "failed": failed, "metrics": metrics, "device": dev}
    if traced:
        from bench.harness import trace

        dev["busy_s"] = rec.trace.busy_s
        dev["window_s"] = rec.trace.window_s
        out["breakdown"] = trace.breakdown(rec.trace)
    out["checks"] = compared
    for name, c in compared.items():
        print(f"check {name} {c['value']} limit {c['limit']}", file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
