"""The serving program's own spans and counters, as a traced run shows
them: a tiny MiTA engine stepped under `jax.profiler.trace` leaves one
``engine.step`` per step, with its number, and the backend's spans
nested inside it; the program's counters agree with a harness-style
wrapper of the backend on the same run; and the readers of the program's
spans and programs give known answers on a hand-made trace and nothing
on the recorded fixture, whose program has no such spans."""

import gc
import gzip
import shutil
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import program, trace
from bench.window import RunData, reader

DATA = Path(__file__).resolve().parent / "data"
W = 8
MS = 1e6                    # ns per ms


def _engine():
    from repro.models import transformer as tfm
    from repro.models.modules import AttnConfig, ModelConfig
    from repro.serve import EngineConfig, ServingEngine
    from repro.serve.backends.mita import MiTABackend

    cfg = ModelConfig(n_layers=2, d_model=64, n_heads=4, n_kv=2, d_ff=128,
                      vocab=97,
                      attn=AttnConfig(window=W, k=W, backend="mita_ref"))
    params = tfm.lm_init(jax.random.PRNGKey(0), cfg)
    ecfg = EngineConfig(n_slots=2, pages_per_slot=5, n_pages=12,
                        prefill_chunk=W, sample_device="fused")
    return ServingEngine(params, cfg, ecfg,
                         backend=MiTABackend(params, cfg, ecfg))


def _requests():
    from repro.serve import Request

    rng = np.random.default_rng(3)
    return [Request(rid=i, prompt=rng.integers(0, 97, n).astype(np.int32),
                    max_new_tokens=g)
            for i, (n, g) in enumerate([(2 * W, 5), (3 * W, 4), (W, 6)])]


def _run(tr=None, lo=0.0, hi=0.0) -> RunData:
    """A run whose traced window is [lo, hi] on the trace's clock."""
    return RunData(spec={}, slots=2, recs={}, dispatches=[], busy=[],
                   step_times=[], t_proc=0.0, w0=0.0, w1=0.0, peak={},
                   trace=tr, t0_trace=lo / 1e9, t1_trace=hi / 1e9,
                   offset_ns=0.0)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A tiny engine's run under the profiler: (engine, the step numbers
    it entered each step with, what a harness-style wrapper of its
    backend saw, the trace's path)."""
    _engine().run(_requests())          # compile outside the trace
    eng = _engine()
    be = eng.backend
    decode, prefill = be.decode_step, be.prefill_chunks
    seen = {"active": [], "rows": [], "valid": []}

    def decode_step(tokens_in, t, active, *a):
        seen["active"].append(int(np.sum(active)))
        return decode(tokens_in, t, active, *a)

    def prefill_chunks(slot_ids, toks, job_active, page_table, t0s,
                       n_valid, n_train):
        act = np.asarray(job_active)
        seen["rows"].append(int(act.sum()))
        seen["valid"].append(int(np.asarray(n_valid)[act].sum()))
        return prefill(slot_ids, toks, job_active, page_table, t0s,
                       n_valid, n_train)

    be.decode_step, be.prefill_chunks = decode_step, prefill_chunks
    for r in _requests():
        eng.submit(r)
    tdir = tmp_path_factory.mktemp("trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    numbers = []
    with jax.profiler.trace(str(tdir), profiler_options=opts):
        while True:
            numbers.append(eng.steps)
            if not eng.step():
                break
        gc.collect()
    return eng, numbers, seen, trace.find_xplane(str(tdir))


def _inside(inner, outer):
    return [o for o in outer if o[1] <= inner[1] and inner[2] <= o[2]]


def test_engine_spans_nest_by_step(traced):
    eng, numbers, _, path = traced
    tr = trace.load(path, program.SPANS)
    tr.program_spans = program.load(path)
    assert sorted(tr.program_spans) == sorted(tr.spans)
    by = {n: [s for s in tr.spans if s[0] == n] for n in program.SPANS}
    steps = by["engine.step"]
    assert len(steps) == len(numbers)
    from jax.profiler import ProfileData
    step_num = [dict(e.stats)["step_num"]
                for p in ProfileData.from_file(path).planes
                for line in p.lines for e in line.events
                if e.name == "engine.step"]
    assert step_num == numbers
    assert by["backend.decode"] and by["engine.prefill"]
    for dec in by["backend.decode"]:
        assert len(_inside(dec, steps)) == 1
        assert len([d for d in by["backend.download"]
                    if _inside(d, [dec])]) == 1
    for down in by["backend.download"]:
        assert _inside(down, by["backend.decode"] + by["backend.prefill"])
    for name in ("engine.admit", "engine.prefill", "engine.pages",
                 "engine.emit", "backend.prefill", "backend.upload"):
        assert by[name], name
        assert all(_inside(s, steps) for s in by[name]), name
    assert by["host.gc"]
    # the host's time per step is part of each step
    lo = min(s for _, s, _ in steps)
    hi = max(e for _, _, e in steps)
    host = program.step_host_ms(_run(tr, lo, hi))
    assert 0 < host < max(e - s for _, s, e in steps) / MS


def test_counters_agree_with_the_harness_wrapper(traced):
    eng, _, seen, _ = traced
    st = eng.stats()
    assert st["decode_dispatches"] == len(seen["active"])
    assert st["decode_slot_steps"] == sum(seen["active"])
    assert st["prefill_rows"] == sum(seen["rows"]) == st["chunks"]
    assert st["prefill_tokens"] == sum(seen["valid"]) == sum(
        len(r.prompt) for r in _requests())
    assert 0 < st["mirror_uploads"] <= st["decode_dispatches"]


def hand_trace():
    """Two engine steps in the window [0, 1000 ms) and one past it; two
    decode programs of 20 ms and a 50 ms prefill program inside it."""
    modules = [["jit_mita_decode_step(7)", 10 * MS, 30 * MS],
               ["jit_mita_decode_step(7)", 40 * MS, 60 * MS],
               ["jit_mita_batched_chunk_prefill(9)", 100 * MS, 150 * MS],
               ["jit_mita_decode_step(7)", 990 * MS, 1100 * MS],
               ["jit_other(3)", 300 * MS, 400 * MS]]
    tr = trace.Trace(ops=[], modules=modules, spans=[])
    tr.program_spans = [
        ["engine.step", 0, 80 * MS],            # 80 - 20 - 10 = 50 host
        ["backend.download", 20 * MS, 40 * MS],
        ["backend.download", 50 * MS, 60 * MS],
        ["engine.step", 90 * MS, 200 * MS],     # 110 - 50 = 60 host
        ["backend.download", 100 * MS, 150 * MS],
        ["engine.step", 950 * MS, 1050 * MS],   # past the window
        ["backend.download", 960 * MS, 1000 * MS]]
    return tr


READ = [("step_host_ms", 55.0), ("step_host_ms.prompt", 55.0),
        ("decode_device_ms", 20.0), ("decode_device_ms.prompt", 20.0),
        ("prefill_device_ms", 50.0)]


@pytest.mark.parametrize("name,want", READ)
def test_program_readers_by_hand(name, want):
    run = _run(hand_trace(), 0, 1000 * MS)
    assert reader(name)(run) == pytest.approx(want)


@pytest.fixture(scope="module")
def chip_trace(tmp_path_factory):
    """The recorded v5e trace, whose program had none of these spans and
    names (``jit_step``, ``jit_run``)."""
    path = tmp_path_factory.mktemp("fixture") / "v5e.xplane.pb"
    with gzip.open(DATA / "v5e.xplane.pb.gz", "rb") as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    from bench.driver import SPANS
    tr = trace.load(str(path), SPANS)
    tr.program_spans = program.load(str(path))
    return tr


@pytest.mark.parametrize("name", [n for n, _ in READ])
def test_program_readers_find_nothing_without_spans(chip_trace, name):
    tr = chip_trace
    assert tr.program_spans == []
    hi = max(e for _, _, e in tr.ops)
    assert reader(name)(_run(tr, tr.anchor_ns(), hi)) is None
    assert reader(name)(_run()) is None
