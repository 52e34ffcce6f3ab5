"""Types shared by the harness (``run.py``) and the traffic drivers."""
from __future__ import annotations

import dataclasses
import math


@dataclasses.dataclass
class Run:
    """What a driver sees of one run."""
    name: str            # the cell
    cell: dict           # bench/workloads/<cell>.json
    config: dict         # bench/configs/<config>.json
    seed: int
    seconds: float

    @property
    def params(self) -> dict:
        return self.cell["params"]

    @property
    def limits(self) -> dict:
        return self.cell["limits"]


@dataclasses.dataclass
class Window:
    """What a driver's measured window hands back."""
    metrics: dict        # end-to-end metric name -> value
    attempted: int
    failed: int
    counters: dict       # what the layer metric readers read
    outputs: object      # what ``verify`` checks, off the device if it can be


@dataclasses.dataclass
class Check:
    """One number compared with its limit; passes when value <= limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value is not None and math.isfinite(self.value) \
            and self.value <= self.limit


def rel_gap(got, ref) -> float:
    """Largest |got - ref| / max(|ref|, 1) (0.0 when empty)."""
    import numpy as np

    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    return float(np.max(np.abs(got - ref) / np.maximum(np.abs(ref), 1.0),
                        initial=0.0))
