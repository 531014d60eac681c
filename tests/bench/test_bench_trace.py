"""The trace reduction: busy union, idle share, kernel time by name and
the host span behind each idle gap — on a hand-made trace with known
answers, and on a small trace recorded on a TPU v5e and kept beside this
file (``data/v5e.xplane.pb.gz``: a traced run of
``qwen3-0.6b`` under long documents, open loop, one-second window)."""

import gzip
import shutil
from pathlib import Path

import numpy as np
import pytest

from bench import trace
from bench.driver import SPANS

DATA = Path(__file__).resolve().parent / "data"
XPLANE = DATA / "v5e.xplane.pb.gz"


def hand_trace():
    ops = [["fusion.1", 0, 10], ["_paged_kernel", 5, 20],
           ["_chunk_kernel", 40, 70], ["fusion.2", 65, 80],
           ["copy", 95, 100]]
    modules = [["jit_step", 0, 25], ["jit_run", 38, 82],
               ["jit_other", 90, 100]]
    spans = [["bench.anchor", 0, 0], ["bench.step", 0, 85],
             ["bench.decode", 1, 30], ["bench.wait", 86, 94]]
    return trace.Trace(ops=ops, modules=modules, spans=spans)


def test_busy_union_and_idle_by_hand():
    tr = hand_trace()
    # busy: [0, 20] + [40, 80] + [95, 100] = 65 of 100
    assert trace.busy_ns(tr, 0, 100) == 65
    assert trace.busy_ns(tr, 10, 50) == 20
    assert trace.gaps(tr, 0, 100) == [(20, 40), (80, 95)]


def test_kernel_time_by_name_by_hand():
    tr = hand_trace()
    assert trace.op_time_ns(tr, "_paged_kernel", 0, 100) == 15
    assert trace.op_time_ns(tr, "_chunk_kernel", 0, 50) == 10
    assert [m[0] for m in trace.modules_with(tr, "_chunk_kernel", 0, 100)
            ] == ["jit_run"]


def test_gap_labels_by_hand():
    tr = hand_trace()
    # gap (20, 40): midpoint 30 inside bench.decode (innermost) and step
    assert trace.label(tr, 20, 40) == "bench.decode"
    # gap (80, 95): midpoint 87.5 inside bench.wait
    assert trace.label(tr, 80, 95) == "bench.wait"
    assert trace.label(tr, 200, 210) == "host"
    b = trace.breakdown(tr, 0, 100)
    assert b["device_ops"][0] == ["_chunk_kernel", 30e-9]
    assert b["idle_gaps"] == [["bench.decode", 20e-9],
                              ["bench.wait", 15e-9]]


@pytest.fixture(scope="module")
def chip_trace(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "v5e.xplane.pb"
    with gzip.open(XPLANE, "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return trace.load(str(path), SPANS)


def _brute_busy(tr, lo, hi, step=1000):
    """Busy nanoseconds on a 1 us grid: independent of `trace.union`."""
    grid = np.zeros(int((hi - lo) // step) + 1, bool)
    for _, s, e in tr.ops:
        a, b = max(s, lo), min(e, hi)
        if b > a:
            grid[int((a - lo) // step): int(np.ceil((b - lo) / step))] = True
    return grid.sum() * step


def test_recorded_trace_reduces(chip_trace):
    tr = chip_trace
    assert tr.devices == 1 and tr.ops and tr.modules
    lo = tr.anchor_ns()
    hi = max(e for _, _, e in tr.ops)
    busy = trace.busy_ns(tr, lo, hi)
    assert 0 < busy < hi - lo
    # the 1 us grid rounds each interval's ends out by under 2 us
    assert abs(_brute_busy(tr, lo, hi) - busy) < 2000 * len(
        trace.union([(s, e) for _, s, e in tr.ops], lo, hi)) + 1
    idle = sum(e - s for s, e in trace.gaps(tr, lo, hi))
    assert idle + busy == pytest.approx(hi - lo)
    # the serving kernels are found by their names in the trace, which
    # are the Pallas calls' names (not the kernel functions')
    for k in ("mita_paged_attention", "mita_chunk_prefill_fused"):
        assert trace.op_time_ns(tr, k, lo, hi) > 0, k
    assert trace.op_time_ns(tr, "_paged_kernel", lo, hi) == 0
    mods = trace.modules_with(tr, "mita_chunk_prefill_fused", lo, hi)
    assert mods and all(e > s for _, s, e in mods)
    labels = {trace.label(tr, s, e) for s, e in trace.gaps(tr, lo, hi)}
    assert labels <= set(SPANS) | {"host"}
    assert labels & {"bench.step", "bench.decode", "bench.prefill"}
