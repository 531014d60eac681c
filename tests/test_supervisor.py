"""Supervisor + chaos unit battery: the failure policy itself.

The conformance suite (tests/test_backend_conformance.py) pins the
cross-backend properties — supervised chaos parity, mid-step leak
freedom — so this file drills the policy mechanics on the cheap MiTA
cell: deterministic schedules, each fault kind's exact lifecycle
(retry / quarantine / ladder rung), deadline + rejection accounting,
stall relief under allocator spikes, straggler counting, the
`AllocatorInvariantError` no-retry contract, and the snapshot/restore
journal (round-trip, file atomicity, and its validation errors).
"""

import dataclasses
import functools
import os
import time

import jax
import numpy as np
import pytest

from repro.models import transformer as tfm
from repro.models.modules import AttnConfig, ModelConfig
from repro.serve import (AllocatorInvariantError, ChaosBackend, ChaosConfig,
                         EngineConfig, InjectedFault, Request, ServingEngine,
                         Supervisor, SupervisorConfig, SupervisionExhausted)
from repro.serve.backends.mita import MiTABackend
from repro.serve.supervisor import DEGRADATION_RUNGS, is_program_build_error

W = 8


@functools.lru_cache(maxsize=None)
def _cell():
    cfg = ModelConfig(
        n_layers=2, d_model=64, n_heads=4, n_kv=2, d_ff=128, vocab=89,
        attn=AttnConfig(window=W, k=W, backend="mita_ref"))
    return cfg, tfm.lm_init(jax.random.PRNGKey(0), cfg)


def _engine(ecfg=None, chaos=None):
    cfg, params = _cell()
    ecfg = ecfg or EngineConfig(n_slots=2, pages_per_slot=4, n_pages=12,
                                prefill_chunk=W)
    backend = MiTABackend(params, cfg, ecfg)
    if chaos is not None:
        backend = ChaosBackend(backend, chaos)
    return ServingEngine(params, cfg, ecfg, backend=backend)


def _requests(specs, seed=7, **kw):
    cfg, _ = _cell()
    rng = np.random.default_rng(seed)
    return [Request(rid=i, prompt=rng.integers(0, cfg.vocab, ln)
                    .astype(np.int32), max_new_tokens=g, **kw)
            for i, (ln, g) in enumerate(specs)]


def _tokens(done):
    return {f.rid: f.tokens.tolist() for f in done
            if f.reason == "complete"}


SPECS = [(W, 4), (2 * W, 6), (W, 3)]


@functools.lru_cache(maxsize=None)
def _reference():
    return tuple(sorted(_tokens(_engine().run(_requests(SPECS))).items()))


def _ref():
    return dict(_reference())


# ----------------------------------------------------------- chaos itself --

def test_chaos_schedule_is_deterministic():
    """Same ChaosConfig + same trace => identical fault schedule, counts,
    and (supervised) identical tokens."""
    chaos = ChaosConfig(seed=9, p_fault=0.3, transient_len=2,
                        p_slot_fault=0.5,
                        ops=("decode_step", "prefill_chunks"))
    outs = []
    for _ in range(2):
        eng = _engine(chaos=chaos)
        sup = Supervisor(eng, SupervisorConfig(max_retries=2))
        done = sup.run(_requests(SPECS))
        outs.append((eng.backend.n_injected, eng.backend.n_faults_started,
                     sup.stats()["retries"], sup.stats()["quarantined"],
                     tuple(sorted(_tokens(done).items()))))
    assert outs[0] == outs[1]
    assert outs[0][0] > 0


def test_chaos_inject_validates():
    cb = ChaosBackend(object(), ChaosConfig())
    with pytest.raises(ValueError, match="unknown op"):
        cb.inject("no_such_op")
    with pytest.raises(ValueError, match="unknown fault kind"):
        cb.inject("decode_step", kind="cosmic_ray")


# ------------------------------------------------------- fault lifecycles --

def test_transient_fault_retries_to_parity():
    """A transient fault is absorbed entirely by the retry loop: no
    quarantine, no rungs, bit-identical streams, counted retries."""
    eng = _engine(chaos=ChaosConfig(transient_len=2))
    sup = Supervisor(eng, SupervisorConfig(max_retries=3))
    cb = eng.backend
    for r in _requests(SPECS):
        sup.submit(r)
    while not eng.active.any():
        sup.step()
    cb.inject("decode_step")        # raises twice, then heals
    while sup.step():
        pass
    st = sup.stats()
    assert _tokens(eng.finished) == _ref()
    assert st["retries"] == 2 and st["quarantined"] == 0
    assert st["degradation_level"] == 0
    assert eng.alloc.in_use == 0 and eng.alloc.refs == {}


def test_slot_fault_quarantines_only_victim():
    """A slot-bound fault evicts ONLY the implicated slot; the victim
    resurrects through recompute-from-prompt bit-identically and the
    rest of the batch never stops."""
    eng = _engine(chaos=ChaosConfig())
    sup = Supervisor(eng, SupervisorConfig(max_retries=1))
    cb = eng.backend
    for r in _requests(SPECS):
        sup.submit(r)
    while not eng.active.any():
        sup.step()
    victim = int(np.nonzero(eng.active)[0][0])
    cb.inject("decode_step", kind="slot", slots=(victim,))
    while sup.step():
        pass
    st = sup.stats()
    assert _tokens(eng.finished) == _ref()
    assert st["quarantined"] == 1
    assert st["degradation_level"] == 0
    assert eng.stats()["preemptions"] >= 1
    assert eng.alloc.in_use == 0 and eng.alloc.refs == {}


def test_persistent_fault_walks_ladder_to_parity():
    """A batch-wide persistent fault climbs exactly as many rungs as it
    takes to clear, the rungs land in stats()/degradations, and the
    degraded engine still gates bit-parity."""
    eng = _engine(chaos=ChaosConfig(persistent_clears_at=2))
    sup = Supervisor(eng, SupervisorConfig(max_retries=1))
    eng.backend.inject("decode_step", kind="persistent")
    done = sup.run(_requests(SPECS))
    st = sup.stats()
    sup.close()
    assert _tokens(done) == _ref()
    assert st["degradation_level"] == 2
    assert sup.degradations == ["spec_off", "prefix_cache_off"]
    assert DEGRADATION_RUNGS[st["degradation_level"]] == "prefix_cache_off"
    assert eng.alloc.in_use == 0


def test_unclearable_fault_exhausts_supervision():
    """A fault nothing clears must end in SupervisionExhausted — loudly,
    not a spin."""
    eng = _engine(chaos=ChaosConfig(persistent_clears_at=99))
    sup = Supervisor(eng, SupervisorConfig(max_retries=1))
    eng.backend.inject("decode_step", kind="persistent")
    with pytest.raises(SupervisionExhausted, match="ladder"):
        sup.run(_requests(SPECS))
    sup.close()


def test_mita_verify_fault_is_retry_safe():
    """MiTA's landmark drafter is stateless, so a verify-step fault can
    be retried without corrupting the stream — the spec'd supervised run
    stays bit-identical to spec_k=0 (the recurrent self-drafters commit
    state at draft time, which is why generic chaos configs gate faults
    at `draft_steps` instead)."""
    base = dataclasses.replace(
        EngineConfig(n_slots=2, pages_per_slot=4, n_pages=16,
                     prefill_chunk=W, sample_device="fused"))
    ref = _tokens(_engine(base).run(_requests(SPECS)))
    ecfg = dataclasses.replace(base, spec_k=3)
    eng = _engine(ecfg, chaos=ChaosConfig(seed=2, p_fault=0.3,
                                          transient_len=2,
                                          ops=("verify_step",)))
    sup = Supervisor(eng, SupervisorConfig(max_retries=3))
    done = sup.run(_requests(SPECS))
    assert _tokens(done) == ref
    assert eng.backend.n_injected > 0
    assert eng.alloc.in_use == 0


# --------------------------------------------- admission robustness paths --

def test_deadline_expired_finishes_with_reason():
    eng = _engine()
    sup = Supervisor(eng)
    reqs = _requests(SPECS)
    ok = [sup.submit(dataclasses.replace(
        r, deadline_ms=0.01 if r.rid == 1 else None)) for r in reqs]
    assert all(ok)
    time.sleep(0.005)
    while sup.step():
        pass
    by_rid = {f.rid: f for f in eng.finished}
    assert by_rid[1].reason == "deadline_expired" and by_rid[1].cancelled
    assert {r: f.tokens.tolist() for r, f in by_rid.items()
            if f.reason == "complete"} \
        == {r: t for r, t in _ref().items() if r != 1}
    assert sup.stats()["deadline_expired"] == 1
    assert eng.alloc.in_use == 0


def test_rejection_surfaces_through_supervisor():
    eng = _engine()
    sup = Supervisor(eng)
    huge = Request(rid=0, prompt=np.zeros(50 * W, np.int32),
                   max_new_tokens=4)
    assert sup.submit(huge) is False
    assert eng.finished[0].reason == "rejected"
    assert sup.stats()["rejected"] == 1


def test_allocator_invariant_error_is_never_retried(monkeypatch):
    eng = _engine()
    sup = Supervisor(eng, SupervisorConfig(max_retries=5))
    monkeypatch.setattr(eng, "step", lambda: (_ for _ in ()).throw(
        AllocatorInvariantError("page accounting corrupt")))
    with pytest.raises(AllocatorInvariantError):
        sup.step()
    assert sup.stats()["retries"] == 0 and sup.n_faults == 0


_BUILD_ERRORS = {
    "mosaic": lambda: jax.errors.JaxRuntimeError(
        "INTERNAL: Mosaic failed to compile TPU kernel: Slice shape along "
        "dimension 1 must be aligned to tiling (8), but is 1."),
    "vmem": lambda: jax.errors.JaxRuntimeError(
        "RESOURCE_EXHAUSTED: Ran out of memory in memory space vmem while "
        "allocating on stack"),
    "lowering": lambda: ValueError(
        "The Pallas TPU lowering currently requires that the last two "
        "dimensions of your block shape are divisible by 8 and 128"),
    "trace": lambda: TypeError("dot_general requires contracting "
                               "dimensions to have the same shape"),
}


@pytest.mark.parametrize("kind", sorted(_BUILD_ERRORS))
def test_program_build_error_leaves_supervisor_at_once(kind, monkeypatch):
    """A compile / lowering / tracing error raised from a backend
    dispatch is deterministic: it leaves `Supervisor.step` on the first
    attempt with no retry, no quarantine and the ladder untouched (the
    xla_forced rung must never paper over a kernel the compiler refused),
    while an injected runtime fault stays a supervised, retried fault."""
    eng = _engine()
    sup = Supervisor(eng, SupervisorConfig(max_retries=5))
    calls = []

    def refuse(*a, **k):
        calls.append(1)
        raise _BUILD_ERRORS[kind]()

    for op in ("prefill_chunks", "prefill_chunk", "prefill_group",
               "decode_step"):
        monkeypatch.setattr(eng.backend, op, refuse)
    env_before = os.environ.get("REPRO_PREFILL_IMPL")
    for r in _requests(SPECS):
        sup.submit(r)
    with pytest.raises(type(_BUILD_ERRORS[kind]())):
        sup.step()
    st = sup.stats()
    assert len(calls) == 1
    assert st["retries"] == 0 and st["degradation_level"] == 0
    assert st["quarantined"] == 0 and sup.n_faults == 0
    assert os.environ.get("REPRO_PREFILL_IMPL") == env_before
    assert is_program_build_error(_BUILD_ERRORS[kind]())
    assert not is_program_build_error(
        InjectedFault("decode_step", [0], "transient"))
    assert not is_program_build_error(jax.errors.JaxRuntimeError(
        "RESOURCE_EXHAUSTED: Error allocating device buffer"))


# -------------------------------------------------- pressure & stragglers --

def test_alloc_spikes_drain_via_stall_relief():
    """Spikes grab REAL pages every dispatch; stall relief must release
    them so the trace completes, with parity and zero leaks."""
    eng = _engine(chaos=ChaosConfig(alloc_spike_every=1,
                                    alloc_spike_pages=3,
                                    alloc_spike_len=50))
    sup = Supervisor(eng, SupervisorConfig(stall_steps=3))
    done = sup.run(_requests(SPECS))
    assert _tokens(done) == _ref()
    assert eng.backend.n_spikes >= 1
    assert eng.alloc.in_use == 0 and eng.alloc.refs == {}


def test_straggler_counter_reaches_stats():
    eng = _engine()
    sup = Supervisor(eng)
    for dt in (0.01, 0.01, 0.01, 0.01, 1.0):
        sup.timer.observe(dt)
    assert sup.stats()["stragglers"] == 1


def test_injected_straggler_is_detected():
    """`p_slow` dispatch delays must trip the shared StepTimer EWMA."""
    chaos = ChaosConfig(seed=4, p_slow=0.12, slow_s=0.3,
                        ops=("decode_step",))
    eng = _engine(chaos=chaos)
    sup = Supervisor(eng, SupervisorConfig(straggler_threshold=3.0))
    done = sup.run(_requests(SPECS))
    assert _tokens(done) == _ref()
    assert eng.backend.n_slowed >= 1
    assert sup.stats()["stragglers"] >= 1


# ------------------------------------------------------------ crash recovery --

def test_snapshot_restore_roundtrip_is_bit_exact(tmp_path):
    """Kill mid-trace, restore on a fresh engine from the journal file:
    the union of pre-kill and post-restore streams is bit-identical to
    the uninterrupted run, counters carry over, deadlines re-arm."""
    eng = _engine(chaos=ChaosConfig(seed=1, p_fault=0.25, transient_len=1,
                                    ops=("decode_step",)))
    sup = Supervisor(eng, SupervisorConfig(max_retries=2))
    for r in _requests(SPECS):
        sup.submit(r)
    for _ in range(5):
        if not sup.step():
            break
    path = str(tmp_path / "snap.json")
    sup.save_snapshot(path)
    assert not os.path.exists(path + ".tmp"), "atomic write left its tmp"
    snap = Supervisor.load_snapshot(path)

    eng2 = _engine()
    sup2 = Supervisor(eng2)
    sup2.restore(snap)
    while sup2.step():
        pass
    assert _tokens(eng2.finished) == _ref()
    assert eng2.n_retries == snap["counters"]["retries"]
    assert eng2.alloc.in_use == 0 and eng2.alloc.refs == {}


def test_restore_validation_errors():
    eng = _engine()
    sup = Supervisor(eng)
    for r in _requests(SPECS):
        sup.submit(r)
    sup.step()
    snap = sup.snapshot()

    with pytest.raises(ValueError, match="fresh engine"):
        sup.restore(snap)           # this engine already has work

    bad = dict(snap, backend="nope")
    with pytest.raises(ValueError, match="backend"):
        Supervisor(_engine()).restore(bad)

    if any(row["tokens"] for row in snap["requests"]):
        mono = _engine(EngineConfig(n_slots=2, pages_per_slot=4,
                                    n_pages=12, prefill_chunk=0))
        with pytest.raises(ValueError, match="chunked prefill"):
            Supervisor(mono).restore(snap)


def test_snapshot_of_drained_engine_restores_finished_only():
    eng = _engine()
    sup = Supervisor(eng)
    sup.run(_requests(SPECS))
    snap = sup.snapshot()
    assert snap["requests"] == []
    eng2 = _engine()
    sup2 = Supervisor(eng2)
    sup2.restore(snap)
    assert not sup2.step()          # nothing to do
    assert _tokens(eng2.finished) == _ref()
