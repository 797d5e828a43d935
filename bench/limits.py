"""Limits of the numbers each cell compares, one file per cell:
``bench/limits/<cell>.json``, ``{number: limit}``.  How each limit was set
from the program's and the control's readings is in ``PERF.md``."""
from __future__ import annotations

import json
import os

LIMITS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "limits")


def limits_of(cell: str) -> dict:
    with open(os.path.join(LIMITS, cell + ".json")) as f:
        return json.load(f)
