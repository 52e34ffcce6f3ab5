"""The benchmark's own tests run on the CPU, at sizes a test can hold."""
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest  # noqa: E402


def small_cell(name: str):
    """(spec, cell, config) of a cell file cut to a CPU test's size: the
    same drivers, checks and limits, a small corpus and build. The spec
    holds the cell, set-up time and the end-to-end metrics BENCHMARK.json
    gives it (none where the cell is not in BENCHMARK.json yet)."""
    from bench import run as R

    full = R._load_json(R.ROOT, "BENCHMARK.json")
    cell = R._load_json(R.BENCH, "workloads", name + ".json")
    config = R._load_json(R.BENCH, "configs", cell["config"] + ".json")
    spec = {"workloads": [{"name": name, "config": cell["config"],
                           "traffic": cell["traffic"], "chips": 1}],
            "end_to_end": [m for m in full["end_to_end"]
                           if name in m.get("workloads", [name])],
            "per_layer": []}
    config["build"].update(s=8, r=24, t1=2, t2=3, capacity=32, chunk=256)
    config["search"].update(l=64, k=32, max_iters=128)
    if config["dim"] == 128:
        config["rows"] = 2048
    else:
        config.update(rows=1024, queries=128, dim=64)
    return spec, cell, config


def run_small(name: str, seed: int = 3, config=None, cell=None,
              seconds: float = 1.0) -> dict:
    import jax

    from bench import run as R

    spec, c, cfg = small_cell(name)
    return R.execute(name, seed, seconds, False, jax.devices(), spec=spec,
                     cell=cell or c, config=config or cfg)


@pytest.fixture(scope="session")
def spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)
