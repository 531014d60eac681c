"""What the serving program reports of itself in a traced run: its own host
spans (`repro.serve.spans`) and its named device programs.

`bench.trace.load` keeps the harness's spans only.  `spans` reads the
program's from the same ``.xplane.pb``, on the same clock, once per run,
and keeps them on the run's trace as ``program_spans``: [name, start_ns,
end_ns], the name cut at any ``#`` metadata.  A run of a program that has
no such spans reads an empty list, and every reduction here then gives
None.
"""

from __future__ import annotations

import bisect
from pathlib import Path

from bench import trace as trace_mod

# listed here, not imported from `repro.serve.spans`: the readers must
# load, and find nothing, on a program that has no such module
SPANS = ("engine.step", "engine.admit", "engine.prefill", "engine.pages",
         "engine.spec", "engine.emit", "backend.decode", "backend.prefill",
         "backend.upload", "backend.download", "supervisor.backoff",
         "host.gc")
# where bench/run.py writes a traced run's trace
TRACE_DIR = Path(__file__).resolve().parent.parent / "bench_out" / "trace"


def load(path: str) -> list:
    """The program's spans in the ``.xplane.pb`` at ``path``."""
    from jax.profiler import ProfileData

    keep = set(SPANS)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                name = e.name.split("#", 1)[0]
                if name in keep:
                    out.append([name, e.start_ns,
                                e.start_ns + e.duration_ns])
    return out


def spans(run) -> list:
    """The program's spans in a traced run (empty when it is not traced,
    or its trace holds none)."""
    tr = run.trace
    if tr is None:
        return []
    if getattr(tr, "program_spans", None) is None:
        try:
            tr.program_spans = load(trace_mod.find_xplane(str(TRACE_DIR)))
        except FileNotFoundError:
            tr.program_spans = []
    return tr.program_spans


def step_host_ms(run):
    """Mean over the ``engine.step`` spans wholly inside the traced window
    of the step's length less the ``backend.download`` spans inside it:
    the host's time per step, in which the device waits on it."""
    sp = spans(run)
    lo, hi = run.trace_bounds_ns()
    steps = [(s, e) for n, s, e in sp
             if n == "engine.step" and lo <= s and e <= hi]
    if not steps:
        return None
    downs = sorted((s, e) for n, s, e in sp if n == "backend.download")
    starts = [s for s, _ in downs]
    host = 0.0
    for s, e in steps:
        i = bisect.bisect_left(starts, s)
        waited = 0.0
        while i < len(downs) and downs[i][1] <= e:
            waited += downs[i][1] - downs[i][0]
            i += 1
        host += (e - s) - waited
    return host / len(steps) / 1e6


def module_ms(run, name: str):
    """Mean device time of the executions of the jitted program ``name``
    (its trace module ``jit_<name>``) wholly inside the traced window."""
    if run.trace is None:
        return None
    lo, hi = run.trace_bounds_ns()
    module = f"jit_{name}"
    durs = [e - s for n, s, e in run.trace.modules
            if n.split("(", 1)[0] == module and lo <= s and e <= hi]
    if not durs:
        return None
    return sum(durs) / len(durs) / 1e6
