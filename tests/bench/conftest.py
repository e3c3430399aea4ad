"""A checkout-shaped directory for running the benchmark's harness on the CPU
at toy widths: the real ``bench/`` and ``src/``, and a ``BENCHMARK.json``
whose cells use the toy configuration and traffic in ``data/``."""
import json
import shutil
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"

if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

TOY_CELLS = {"toy.decode": "tiny_decode", "toy.prefix": "tiny_prefix",
             "toy.train": "tiny_train"}
TOY_LIMITS = {"toy.decode": "limits_tiny", "toy.prefix": "limits_tiny",
              "toy.train": "limits_tiny_train"}


@pytest.fixture(scope="session")
def toy_root(tmp_path_factory) -> Path:
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "src").symlink_to(REPO / "src")
    shutil.copy(DATA / "tiny.json", root / "bench" / "configs" / "toy.json")
    for cell, traffic in TOY_CELLS.items():
        shutil.copy(DATA / f"{traffic}.json",
                    root / "bench" / "traffic" / f"{traffic}.json")
        shutil.copy(DATA / f"{TOY_LIMITS[cell]}.json",
                    root / "bench" / "limits" / f"{cell}.json")
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    rename = {"danube3.decode_heavy": "toy.decode",
              "danube3.shared_prefix": "toy.prefix"}
    spec["configs"] = [{"name": "toy", "source": "toy",
                        "file": "bench/configs/toy.json", "reduced": [],
                        "why": "toy"}]
    spec["workloads"] = [{"name": c, "config": "toy", "traffic": t,
                          "chips": 4 if c == "toy.train" else 1, "why": "toy"}
                         for c, t in TOY_CELLS.items()]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [rename[w] for w in m["workloads"] if w in rename]
    # the training cell's metrics, whose readers are in bench/metrics
    spec["end_to_end"].append({"name": "train_tok_s", "unit": "tokens/s",
                               "better": "higher", "bound": 0.05,
                               "source": "host_clock", "workloads": ["toy.train"]})
    spec["per_layer"] += [
        {"name": n, "unit": "%", "better": "higher", "source": "device_trace",
         "layer": "device", "moves": "train_tok_s", "workloads": ["toy.train"]}
        for n in ("idle_share.train", "mfu.train",
                  "collective_exposed_share.train")]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root
