"""Reduction of a profiler trace to device busy time, kernel time and the
host span behind each idle gap.

`load` reads the ``.xplane.pb`` that `jax.profiler` writes, keeping three
kinds of event, all in nanoseconds on the trace's clock:

  * ``ops``      device operations (the TPU planes' "XLA Ops" lines),
                 named by `short_name`;
  * ``modules``  device program executions ("XLA Modules" lines);
  * ``spans``    the benchmark's host spans (`bench.driver.SPANS`) and its
                 ``bench.anchor`` mark, which ties the trace's clock to the
                 host clock the load generator stamps with.

`Trace` holds them as plain lists; the reductions below take a window
[lo, hi] on the trace's clock.
"""

from __future__ import annotations

import dataclasses
import glob
import os

ANCHOR = "bench.anchor"
# operations that only hold others (a layer scan, a branch): left out of
# the breakdown's list, which would otherwise count their bodies twice
CONTAINERS = ("while.", "conditional.", "call.")


@dataclasses.dataclass
class Trace:
    ops: list           # [name, start_ns, end_ns]
    modules: list       # [name, start_ns, end_ns]
    spans: list         # [name, start_ns, end_ns]
    devices: int = 1

    def anchor_ns(self) -> float:
        for name, s, _ in self.spans:
            if name == ANCHOR:
                return s
        raise ValueError("the trace holds no bench.anchor span")


def find_xplane(trace_dir: str) -> str:
    hits = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return hits[-1]


def short_name(hlo: str) -> str:
    """A device operation's event name is its HLO instruction text; keep
    the instruction's name and the first shape of its result, e.g.
    ``copy.141 f32[28,24577,8,128]``."""
    name, _, rest = hlo.partition(" = ")
    shape = rest.lstrip("(").split("{", 1)[0].split(" ", 1)[0]
    return f"{name.lstrip('%')} {shape}".strip()


def load(path: str, span_names) -> Trace:
    from jax.profiler import ProfileData

    keep = set(span_names) | {ANCHOR}
    pd = ProfileData.from_file(path)
    ops, modules, spans = [], [], []
    devices = 0
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            devices += 1
            for line in plane.lines:
                dest = {"XLA Ops": ops, "XLA Modules": modules}.get(
                    line.name)
                if dest is None:
                    continue
                for e in line.events:
                    dest.append([short_name(e.name), e.start_ns,
                                 e.start_ns + e.duration_ns])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in keep:
                        spans.append([e.name, e.start_ns,
                                      e.start_ns + e.duration_ns])
    return Trace(ops=ops, modules=modules, spans=spans,
                 devices=max(devices, 1))


# ---------------------------------------------------------- reduction --

def union(intervals, lo: float, hi: float) -> list:
    """Merged [start, end] intervals, clipped to [lo, hi]."""
    out: list = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_ns(tr: Trace, lo: float, hi: float) -> float:
    """Device busy time in [lo, hi]: the union of operation intervals,
    averaged over the traced devices."""
    return sum(e - s for s, e in union(
        [(s, e) for _, s, e in tr.ops], lo, hi)) / tr.devices


def gaps(tr: Trace, lo: float, hi: float) -> list:
    """Idle intervals of the device in [lo, hi]."""
    busy = union([(s, e) for _, s, e in tr.ops], lo, hi)
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def label(tr: Trace, s: float, e: float) -> str:
    """The innermost benchmark host span open at the gap's midpoint
    ("host" when none is)."""
    mid = (s + e) / 2
    best, width = "host", float("inf")
    for name, a, b in tr.spans:
        if name != ANCHOR and a <= mid <= b and b - a < width:
            best, width = name, b - a
    return best


def op_time_ns(tr: Trace, needle: str, lo: float, hi: float) -> float:
    """Summed device time of operations whose name contains ``needle``,
    clipped to [lo, hi]."""
    return sum(max(0.0, min(e, hi) - max(s, lo)) for n, s, e in tr.ops
               if needle in n)


def modules_with(tr: Trace, needle: str, lo: float, hi: float) -> list:
    """Device program executions in [lo, hi] that contain an operation
    whose name contains ``needle``."""
    hits = sorted((s, e) for n, s, e in tr.ops
                  if needle in n and lo <= s < hi)
    out, i = [], 0
    for name, s, e in sorted(tr.modules, key=lambda m: m[1]):
        if not (lo <= s < hi):
            continue
        while i < len(hits) and hits[i][0] < s:
            i += 1
        if i < len(hits) and hits[i][0] < e:
            out.append((name, s, e))
    return out


def breakdown(tr: Trace, lo: float, hi: float, top: int = 10) -> dict:
    """The device operations that took most time, and the longest idle
    gaps with the host span behind each, in seconds."""
    by_name: dict = {}
    for n, s, e in tr.ops:
        if n.startswith(CONTAINERS):
            continue
        d = min(e, hi) - max(s, lo)
        if d > 0:
            by_name[n] = by_name.get(n, 0.0) + d
    ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(gaps(tr, lo, hi), key=lambda g: g[0] - g[1])[:top]
    return {"device_ops": [[n, d / 1e9 / tr.devices] for n, d in ops],
            "idle_gaps": [[label(tr, s, e), (e - s) / 1e9]
                          for s, e in idle]}
