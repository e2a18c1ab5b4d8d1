"""The numbers ``correct`` rests on, and the limits they are held to.

Each comparison is a relative L2 distance, ``|got - want| / |want|`` over
all the elements compared: steadier from seed to seed than a maximum, and
it separates a bf16 program from an fp8 one by more than an order of
magnitude (PERF.md section 2 gives both readings for every limit).
"""

from __future__ import annotations

import json
import math
from typing import Dict, List

import numpy as np


def rel_l2(got, want) -> float:
    got = np.asarray(got, np.float64).ravel()
    want = np.asarray(want, np.float64).ravel()
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


def rel_abs(got: float, want: float) -> float:
    return abs(float(got) - float(want)) / max(abs(float(want)), 1e-30)


class Verdict:
    """Collects ``name: value <= limit`` lines; ``correct`` is their
    conjunction. Every number is printed beside its limit."""

    def __init__(self):
        self.rows: List[Dict] = []

    def hold(self, name: str, value: float, limit: float) -> None:
        ok = bool(math.isfinite(value) and value <= limit)
        self.rows.append({"check": name, "value": value, "limit": limit,
                          "ok": ok})

    def require(self, name: str, cond: bool, detail: str = "") -> None:
        self.rows.append({"check": name, "value": 0.0 if cond else 1.0,
                          "limit": 0.0, "ok": bool(cond), "detail": detail})

    @property
    def correct(self) -> bool:
        return bool(self.rows) and all(r["ok"] for r in self.rows)

    def print(self, out) -> None:
        for r in self.rows:
            print(json.dumps({"compared": r}), file=out, flush=True)
