"""What one run hands back, and the lines it prints.

The last line of stdout is the run's JSON result; the numbers that
decide ``correct`` go last in it (``checks``) and are also the last lines
of stderr, each beside its limit.
"""
from __future__ import annotations

import dataclasses
import json
import sys
from typing import Any


@dataclasses.dataclass
class Check:
    value: float
    limit: float
    why: str

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclasses.dataclass
class Outcome:
    setup_s: float
    end_to_end: dict[str, float]
    attempted: int
    failed: int
    checks: dict[str, Check]
    memory_peak_bytes: int
    setup_split: dict[str, Any]
    data: dict[str, Any]              # what the per-layer readers read
    trace: dict | None = None         # trace.reduce output (--trace 1)


def device_info(chips: int, memory_peak: int, trace: dict | None) -> dict:
    import jax
    devs = jax.devices()[:chips]
    out = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs), "memory_peak_bytes": int(memory_peak)}
    if trace is not None:
        out["busy_s"] = trace["busy_s"]
        out["window_s"] = trace["window_s"]
    return out


def emit(out: Outcome, metrics: dict, device: dict):
    """Print the setup split, the checks and the result line."""
    print("setup split: " + json.dumps(out.setup_split), file=sys.stderr)
    for name, c in out.checks.items():
        print(f"check {name}: {c.value!r} <= {c.limit!r} "
              f"{'ok' if c.ok else 'FAILED'} ({c.why})", file=sys.stderr)
    line = {"correct": all(c.ok for c in out.checks.values()),
            "attempted": out.attempted, "failed": out.failed,
            "metrics": metrics, "device": device}
    if out.trace is not None:
        line["breakdown"] = {"device_ops": out.trace["ops"],
                             "idle_gaps": out.trace["gaps"]}
    line["checks"] = {k: {"value": c.value, "limit": c.limit}
                      for k, c in out.checks.items()}
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
