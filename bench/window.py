"""What one run recorded, as the metric readers see it.

Every metric, end to end or per layer, is a reader of its own in
``bench/metrics/<name>.py`` with one function, ``read(run) -> float |
None``, where ``run`` is a `RunData`.  A reader that finds nothing to read
returns None, and the harness leaves that metric out of the result line.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import math
from pathlib import Path
from typing import Any, Optional

import numpy as np

METRICS = Path(__file__).resolve().parent / "metrics"


@dataclasses.dataclass
class RunData:
    spec: dict                  # configuration file
    slots: int
    recs: dict                  # rid -> driver.Rec
    dispatches: list            # driver.Dispatch, in issue order
    busy: list                  # (start, end) host spans with work
    step_times: list            # the engine's decode step times, in window
    t_proc: float               # process start (host clock)
    w0: float                   # measured window, host clock
    w1: float
    peak: dict                  # the chip's row of bench/peaks.json
    trace: Any = None           # bench.trace.Trace, traced runs only
    t0_trace: float = math.nan  # traced window, host clock
    t1_trace: float = math.nan
    offset_ns: float = math.nan  # trace clock - host clock, ns

    @property
    def seconds(self) -> float:
        return self.w1 - self.w0

    # ------------------------------------------------------ requests --

    def due_in_window(self) -> list:
        return [r for r in self.recs.values()
                if self.w0 <= r.due < self.w1]

    def window_dispatches(self, kind: str, lo: Optional[float] = None,
                          hi: Optional[float] = None) -> list:
        lo = self.w0 if lo is None else lo
        hi = self.w1 if hi is None else hi
        return [d for d in self.dispatches
                if d.kind == kind and d.t0 >= lo and d.t1 <= hi]

    def traced_dispatches(self, kind: str) -> list:
        return self.window_dispatches(kind, self.t0_trace, self.t1_trace)

    # --------------------------------------------------------- trace --

    def trace_bounds_ns(self) -> tuple[float, float]:
        return (self.t0_trace * 1e9 + self.offset_ns,
                self.t1_trace * 1e9 + self.offset_ns)


def percentile(values, q: float) -> Optional[float]:
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def reader(name: str, metrics_dir: Path = METRICS):
    """The ``read`` function of ``bench/metrics/<name>.py``."""
    path = metrics_dir / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
