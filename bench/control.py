"""The control of a cell's correctness checks: the cell run with the program's
own lower-precision path switched on (the cell file's ``control``: the
configuration keys it overrides, such as ``gram_dtype = "bf16"`` where the
configuration states float32). Its checks have to fail.

    python3 bench/control.py --workload <cell> --seed <n> --seconds <s>
    python3 bench/control.py ... --fault <name>

``--fault`` runs one of the cell file's ``faults`` instead: configuration
keys that break the timed path (a beam cut short), whose checks have to
fail too. Prints the checks, each beside its limit, and the result line.
The benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import copy
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import run as R  # noqa: E402


def control_config(cell: dict, config: dict, fault: str | None = None) -> dict:
    """The configuration with the cell's control overrides applied, or
    those of its fault ``fault``."""
    out = copy.deepcopy(config)
    over = cell["control"] if fault is None else cell["faults"][fault]
    for group, keys in over.items():
        out[group].update(keys)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fault", default=None)
    args = ap.parse_args(argv)
    import jax

    spec = R._load_json(R.ROOT, "BENCHMARK.json")
    entry, cell, config = R.load_cell(args.workload, spec)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < entry["chips"]:
        print("bench/control.py: no TPU; nothing was run", file=sys.stderr)
        return 2
    R.enable_compile_cache()
    out = R.execute(args.workload, args.seed, args.seconds, False,
                    devices[:entry["chips"]], spec=spec,
                    config=control_config(cell, config, args.fault))
    for name, c in out["checks"].items():
        print(f"{args.fault or 'control'} check {name} = {c['value']!r} (limit "
              f"{c['limit']!r})", file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
