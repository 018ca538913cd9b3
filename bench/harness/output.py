"""The result line and the compared numbers beside their limits."""
from __future__ import annotations

import json
import math
import sys


def emit(checks, attempted: int, failed: int, metrics: dict, device: dict,
         breakdown=None) -> None:
    """Print each compared number with its limit as the last lines of
    standard error, then the result line as the last line of standard
    output.  ``checks``: (name, value, limit) triples; the run is correct
    when every value is finite and within its limit and nothing failed."""
    correct = failed == 0 and all(
        math.isfinite(v) and v <= lim for _, v, lim in checks)
    for name, v, lim in checks:
        print(f"check {name} = {v!r} (limit {lim!r})", file=sys.stderr)
    sys.stderr.flush()
    line = {"correct": correct, "attempted": attempted, "failed": failed,
            "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = {name: {"value": v, "limit": lim}
                      for name, v, lim in checks}
    print(json.dumps(line), flush=True)
