"""Backend conformance: ONE shared battery against every `DecodeBackend`.

Every test below runs once per backend through the single ``cell``
fixture — adding a backend to ``BACKENDS`` subjects it to the whole
contract with zero new test code:

  * alloc → prefill → decode greedy tokens == the backend's own
    static/full-forward reference (chunked admission, slot reuse);
  * preempt → recompute parity: an evicted victim re-emits identical
    tokens;
  * retire releases EVERYTHING: no page, slot, or refcount survives a
    drained trace;
  * ``stats()`` returns exactly the centralized schema
    (`serve.backends.STATS_SCHEMA`) — bench rows and dashboards can key
    on it without per-backend special cases;
  * the speculative triple (draft/verify/rollback): streams with
    ``spec_k > 0`` are bit-identical to ``spec_k = 0`` in every drafting
    mode the backend supports (the recurrent backends' synthetic "stress"
    mode forces rejections so rollback is genuinely exercised);
  * a hypothesis schedule fuzzer: random prompts/lengths/spec_k with
    cancel injection, parity + allocator-leak invariants on every run.
"""

import dataclasses
import functools

import jax
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro.models import mamba2 as m2
from repro.models import rglru as rglru_mod
from repro.models import transformer as tfm
from repro.models.modules import AttnConfig, ModelConfig
from repro.serve import (ChaosBackend, ChaosConfig, EngineConfig,
                         InjectedFault, Request, ServingEngine, Supervisor,
                         SupervisorConfig)
from repro.serve.backends import (BACKEND_STAT_KEYS, ENGINE_STAT_KEYS,
                                  STATS_SCHEMA, BackendBase)
from repro.serve.backends.mita import MiTABackend
from repro.serve.backends.recurrent import Mamba2Backend, RGLRUBackend

W = 8
BACKENDS = ("mita", "mamba2", "rglru")
# drafting modes each backend supports (mita's "auto" = landmark
# self-draft; recurrent "self" never rejects, "stress" always does)
SPEC_MODES = {"mita": ("auto",), "mamba2": ("self", "stress"),
              "rglru": ("self", "stress")}


@functools.lru_cache(maxsize=None)
def _cell(name):
    key = jax.random.PRNGKey(0)
    if name == "mita":
        cfg = ModelConfig(
            n_layers=2, d_model=64, n_heads=4, n_kv=2, d_ff=128, vocab=97,
            attn=AttnConfig(window=W, k=W, backend="mita_ref"))
        return cfg, tfm.lm_init(key, cfg), MiTABackend
    if name == "mamba2":
        cfg = ModelConfig(
            n_layers=2, d_model=32, n_heads=1, n_kv=1, d_ff=0, vocab=97,
            attn=AttnConfig(window=W, backend="full"))
        return cfg, m2.mamba_init(key, cfg), Mamba2Backend
    cfg = ModelConfig(
        n_layers=3, d_model=64, n_heads=4, n_kv=2, d_ff=128, vocab=97,
        attn=AttnConfig(window=W, k=W, backend="mita_ref"))
    return cfg, rglru_mod.rg_init(key, cfg), RGLRUBackend


@pytest.fixture(params=BACKENDS)
def cell(request):
    """THE conformance fixture: ``(name, cfg, params, engine factory)``."""
    name = request.param
    cfg, params, mk = _cell(name)

    def engine(ecfg):
        return ServingEngine(params, cfg, ecfg,
                             backend=mk(params, cfg, ecfg))

    return name, cfg, params, engine


def _requests(vocab, specs, temperature=0.0, seed=7):
    rng = np.random.default_rng(seed)
    return [Request(rid=i, prompt=rng.integers(0, vocab, ln).astype(np.int32),
                    max_new_tokens=g, temperature=temperature)
            for i, (ln, g) in enumerate(specs)]


def _tokens(done):
    return {f.rid: f.tokens.tolist() for f in done if not f.cancelled}


# --------------------------------------------------------------- the battery

def test_alloc_prefill_decode_reference_parity(cell):
    """Chunked admission with slot reuse: every request's greedy stream is
    bit-identical to the backend's static/full-forward reference."""
    name, cfg, params, engine = cell
    reqs = _requests(cfg.vocab, [(W, 4), (2 * W, 7), (3 * W, 3), (W, 6)])
    ecfg = EngineConfig(n_slots=2, pages_per_slot=5, n_pages=12,
                        prefill_chunk=W)
    eng = engine(ecfg)
    done = eng.run(reqs)
    assert len(done) == len(reqs)
    ref = eng.backend.fresh()
    for f, r in zip(sorted(done, key=lambda f: f.rid), reqs):
        expect = ref.static_reference(r.prompt[None], r.max_new_tokens)
        np.testing.assert_array_equal(f.tokens, expect[0],
                                      err_msg=f"{name} req {f.rid}")


def test_preempt_recompute_parity(cell):
    """A low-priority victim evicted mid-decode by high-priority arrivals
    re-emits exactly the stream it would have produced unpreempted."""
    name, cfg, params, engine = cell
    victim = np.asarray(jax.random.randint(jax.random.PRNGKey(3), (2 * W,),
                                           0, cfg.vocab))
    ecfg = EngineConfig(n_slots=2, pages_per_slot=6, n_pages=8,
                        prefill_chunk=2 * W)
    ref = engine(ecfg).run(
        [Request(rid=0, prompt=victim, max_new_tokens=16)])[0].tokens

    eng = engine(ecfg)
    eng.submit(Request(rid=0, prompt=victim, max_new_tokens=16, priority=0))
    for _ in range(6):
        eng.step()
    hp = jax.random.randint(jax.random.PRNGKey(5), (2, 2 * W), 0, cfg.vocab)
    for i in (1, 2):
        eng.submit(Request(rid=i, prompt=np.asarray(hp[i - 1]),
                           max_new_tokens=16, priority=5))
    while eng.step():
        pass
    done = sorted(eng.finished, key=lambda f: f.rid)
    assert len(done) == 3
    assert eng.n_preemptions >= 1, "scenario no longer triggers preemption"
    np.testing.assert_array_equal(done[0].tokens, ref,
                                  err_msg=f"{name} victim diverged")


def test_retire_releases_everything(cell):
    """After a drained trace: zero pages in use, zero refcounts, every
    slot free, nothing active — for every backend, cache off."""
    name, cfg, params, engine = cell
    ecfg = EngineConfig(n_slots=3, pages_per_slot=5, n_pages=15,
                        prefill_chunk=W)
    eng = engine(ecfg)
    eng.run(_requests(cfg.vocab, [(W, 3), (2 * W, 5), (W, 2), (2 * W, 4)]))
    assert eng.alloc.in_use == 0, f"{name}: pages leaked"
    assert eng.alloc.refs == {}, f"{name}: refcounts leaked"
    assert sorted(eng.alloc.free) == list(range(ecfg.n_pages))
    assert not eng.active.any() and not eng.slot_pages
    assert sorted(eng.free_slots) == list(range(ecfg.n_slots))


def test_stats_schema_is_exact(cell):
    """`stats()` returns EXACTLY the centralized schema — the engine's
    scheduler counters plus the backend counters, no drift either way —
    and the backend's own `stats()` covers `BACKEND_STAT_KEYS`."""
    name, cfg, params, engine = cell
    eng = engine(EngineConfig(n_slots=2, pages_per_slot=4, n_pages=8,
                              prefill_chunk=W))
    eng.run(_requests(cfg.vocab, [(W, 2)]))
    st = eng.stats()
    assert set(st) == STATS_SCHEMA, (
        f"{name}: stats keys drifted from serve.backends.STATS_SCHEMA: "
        f"extra={set(st) - STATS_SCHEMA} missing={STATS_SCHEMA - set(st)}")
    assert set(eng.backend.stats()) == BACKEND_STAT_KEYS
    assert st["backend"] == name
    assert "backend" in ENGINE_STAT_KEYS


def test_speculative_parity_all_modes(cell):
    """The draft/verify/rollback triple is LOSSLESS: with any supported
    spec_mode and spec_k, greedy and tempered streams are bit-identical to
    the spec_k=0 engine, requests retire after the same number of emitted
    tokens, and the accept/rollback counters are consistent."""
    name, cfg, params, engine = cell
    specs = [(W, 5), (2 * W - 3, 9), (2 * W, 4), (5, 11)]
    for temp in (0.0, 0.8):
        base_ecfg = EngineConfig(n_slots=3, pages_per_slot=4, n_pages=24,
                                 prefill_chunk=W, sample_device="fused")
        base = _tokens(engine(base_ecfg).run(
            _requests(cfg.vocab, specs, temperature=temp)))
        for mode in SPEC_MODES[name]:
            eng = engine(dataclasses.replace(base_ecfg, spec_k=3,
                                             spec_mode=mode))
            got = _tokens(eng.run(_requests(cfg.vocab, specs,
                                            temperature=temp)))
            assert got == base, (f"{name} spec_mode={mode} temp={temp} "
                                 "diverged from spec_k=0")
            st = eng.stats()
            assert st["spec_accepted"] <= st["spec_drafted"]
            # a rollback implies >= 1 drafted-but-rejected token
            assert st["spec_rollbacks"] \
                <= st["spec_drafted"] - st["spec_accepted"]
            if mode == "self":       # exact self-drafts never reject
                assert st["spec_rollbacks"] == 0
                assert st["spec_accepted"] == st["spec_drafted"] > 0
            if mode == "stress":     # synthetic drafts exercise rollback
                assert st["spec_rollbacks"] > 0


def _host_array(values, dtype):
    """``values`` in a fresh 64-byte-aligned numpy array: on the CPU, JAX
    reads such an array in place instead of copying it, as it may any
    engine-owned array."""
    values = np.asarray(values, dtype)
    buf = np.empty(values.size * values.itemsize + 64, np.uint8)
    off = -buf.ctypes.data % 64
    out = buf[off:off + values.size * values.itemsize].view(dtype)
    out[:] = values
    return out


@pytest.mark.parametrize("name", ["mamba2", "rglru"])
def test_stress_rollback_owns_its_inputs(name):
    """`rollback` is dispatched with no read-back, and the engine then
    advances its host ``t`` / ``sample_idx`` / ``tokens_in`` / ``active``
    in place.  On the CPU, JAX may read a host array in place when the
    program runs, which can be after the computations queued ahead of
    it, so the backend must keep private copies: here the host arrays
    are overwritten right after `rollback` while a long computation
    holds the queue, and the rolled-back state must equal the one from
    untouched inputs."""
    import jax.numpy as jnp

    cfg, params, mk = _cell(name)
    ecfg = EngineConfig(n_slots=2, pages_per_slot=4, n_pages=8,
                        sample_device="fused", spec_k=3, spec_mode="stress")
    busy = jax.jit(lambda m: m @ m @ m @ m @ m @ m)
    big = jnp.full((1024, 1024), 1e-3, jnp.float32)

    def rolled(clobber):
        b = mk(params, cfg, ecfg)
        tok, t = _host_array([5, 9], np.int32), _host_array([2, 6], np.int32)
        active = _host_array([True, True], np.bool_)
        rid, temp = np.array([0, 1], np.int32), np.zeros(2, np.float32)
        si = _host_array([2, 6], np.int32)
        spec_len, key = np.array([3, 3], np.int32), jax.random.PRNGKey(1)
        drafts = b.draft_steps(tok, t, active, None, rid, temp, si, key,
                               spec_len)
        b.verify_step(tok, t, active, None, rid, temp, si, key, spec_len,
                      drafts)
        hold = busy(big)
        b.rollback(np.array([1, 2], np.int32), active)
        if clobber:                 # what the engine does next, in place
            for a in (tok, t, si):
                a += 7
            active[:] = False
        hold.block_until_ready()
        return jax.tree.map(np.asarray, b.states)

    ref = rolled(False)
    for _ in range(3):
        jax.tree.map(np.testing.assert_array_equal, rolled(True), ref)


def test_speculation_contract_surface(cell):
    """Protocol surface: the backend advertises `supports_speculation`,
    `draft_horizon` returns a per-slot nonnegative int array, and the
    engine refuses spec_k > 0 without fused sampling."""
    name, cfg, params, engine = cell
    eng = engine(EngineConfig(n_slots=2, pages_per_slot=4, n_pages=8))
    assert eng.backend.supports_speculation
    h = eng.backend.draft_horizon(np.array([0, 5, W - 1, W, 3 * W + 2]))
    assert h.shape == (5,) and np.issubdtype(h.dtype, np.integer)
    assert (h >= 0).all()
    with pytest.raises(ValueError, match="fused"):
        engine(EngineConfig(n_slots=2, pages_per_slot=4, n_pages=8,
                            spec_k=2))


def test_base_backend_refuses_speculation():
    """A backend that does not override the triple raises, and the engine
    rejects spec_k > 0 against it up front."""
    b = BackendBase(None, None, EngineConfig())
    assert not b.supports_speculation
    for call in (lambda: b.draft_steps(*[None] * 9),
                 lambda: b.verify_step(*[None] * 10),
                 lambda: b.rollback(None, None)):
        with pytest.raises(NotImplementedError, match="speculative"):
            call()
    # the default horizon is unbounded (no backend-internal boundary)
    assert (b.draft_horizon(np.zeros(3, np.int32))
            == np.iinfo(np.int32).max).all()


# ------------------------------------------------ fault & leak conformance --

def test_midstep_exception_leaks_no_pages(cell):
    """A backend raising mid-`step()` must leave the scheduler consistent:
    after the exception propagates, draining the SAME engine returns the
    pool to zero pages / zero refcounts and every stream still matches the
    static reference.  All three dispatch sites are exercised — monolithic
    admission (`prefill_group`, the rollback path), chunked prefill, and
    decode — for every backend."""
    name, cfg, params, engine = cell
    mkcls = _cell(name)[2]
    specs = [(W, 3), (2 * W, 4)]
    for chunk, op in ((0, "prefill_group"), (W, "prefill_chunks"),
                      (W, "decode_step")):
        ecfg = EngineConfig(n_slots=2, pages_per_slot=4, n_pages=10,
                            prefill_chunk=chunk)
        cb = ChaosBackend(mkcls(params, cfg, ecfg), ChaosConfig())
        eng = ServingEngine(params, cfg, ecfg, backend=cb)
        for r in _requests(cfg.vocab, specs):
            eng.submit(r)
        if op == "decode_step":     # land the fault after prefill finished
            while not eng.active.any():
                eng.step()
        cb.inject(op, raises=1)
        with pytest.raises(InjectedFault):
            while eng.step():
                pass
        while eng.step():           # fault healed: same engine drains
            pass
        assert eng.alloc.in_use == 0, f"{name}/{op}: pages leaked"
        assert eng.alloc.refs == {}, f"{name}/{op}: refcounts leaked"
        ref = cb.inner.fresh()
        for f, r in zip(sorted(eng.finished, key=lambda f: f.rid),
                        _requests(cfg.vocab, specs)):
            np.testing.assert_array_equal(
                f.tokens, ref.static_reference(r.prompt[None],
                                               r.max_new_tokens)[0],
                err_msg=f"{name}/{op}: stream diverged after fault")


def test_supervised_chaos_parity(cell):
    """Seeded chaos (transient + slot-bound faults + allocator spikes)
    under the supervisor: every request completes bit-identical to the
    fault-free engine, the pool drains to zero, and the robustness
    counters in `stats()` actually move — for every backend."""
    name, cfg, params, engine = cell
    mkcls = _cell(name)[2]
    specs = [(W, 4), (2 * W, 6), (W, 3), (2 * W, 5)]
    ecfg = EngineConfig(n_slots=2, pages_per_slot=4, n_pages=12,
                        prefill_chunk=W)
    ref = _tokens(engine(ecfg).run(_requests(cfg.vocab, specs)))
    chaos = ChaosConfig(seed=5, p_fault=0.3, transient_len=2,
                        p_slot_fault=0.4, alloc_spike_every=5,
                        alloc_spike_pages=2,
                        ops=("decode_step", "prefill_chunks"))
    cb = ChaosBackend(mkcls(params, cfg, ecfg), chaos)
    eng = ServingEngine(params, cfg, ecfg, backend=cb)
    sup = Supervisor(eng, SupervisorConfig(max_retries=2, stall_steps=4))
    done = sup.run(_requests(cfg.vocab, specs))
    sup.close()
    assert _tokens(done) == ref, f"{name}: supervised streams diverged"
    assert eng.alloc.in_use == 0 and eng.alloc.refs == {}
    assert cb.n_injected > 0, f"{name}: chaos schedule fired nothing"
    assert sup.stats()["retries"] > 0


# ------------------------------------------------------- schedule fuzzing --

@settings(max_examples=6, deadline=None)
@given(st.sampled_from(["mita", "mamba2"]), st.integers(1, 4),
       st.booleans(), st.booleans(), st.integers(0, 2**31 - 1))
def test_speculative_schedule_fuzz(name, spec_k, cancel, chaos, seed):
    """Property: ANY random schedule — prompt lengths, generation budgets,
    staggered arrivals, optional mid-trace cancellation, optional seeded
    chaos (supervised transient/slot faults + allocator spikes) — produces
    token streams bit-identical to the fault-free spec_k=0 engine for
    every request that ran to completion, and the allocator ends every
    trace with zero pages in use (mita exercises the landmark drafter;
    mamba2 the stress mode, so rollback replay is fuzzed too).  Chaos only
    intercepts ops whose faults fire BEFORE any state mutation
    (`draft_steps` is gated pre-draft, never `verify_step`), so a retried
    step replays against unchanged backend state by construction."""
    cfg, params, mk = _cell(name)
    rng = np.random.default_rng(seed)
    servable = [5, 6, W, W + 2, 2 * W - 2, 2 * W]
    specs = [(int(rng.choice(servable)), int(rng.integers(2, 10)))
             for _ in range(5)]
    mode = "auto" if name == "mita" else "stress"

    def run(k, with_chaos):
        ecfg = EngineConfig(n_slots=2, pages_per_slot=4, n_pages=16,
                            prefill_chunk=W, sample_device="fused",
                            spec_k=k, spec_mode=mode if k else "auto")
        backend = mk(params, cfg, ecfg)
        cb = None
        if with_chaos:
            backend = cb = ChaosBackend(backend, ChaosConfig(
                seed=seed ^ 0xC0FFEE, p_fault=0.2, transient_len=2,
                p_slot_fault=0.3, alloc_spike_every=7, alloc_spike_pages=2,
                ops=("decode_step", "prefill_chunks", "draft_steps")))
        eng = ServingEngine(params, cfg, ecfg, backend=backend)
        sup = Supervisor(eng, SupervisorConfig(max_retries=2,
                                               stall_steps=4)) \
            if with_chaos else None
        step = sup.step if sup is not None else eng.step
        pend = _requests(cfg.vocab, specs, seed=seed)
        idx = steps = 0
        while idx < len(pend) or eng.waiting or eng.prefilling \
                or eng.active.any():
            while idx < len(pend) and idx <= steps:
                eng.submit(pend[idx])
                idx += 1
            if cancel and steps == 3:
                eng.cancel(1)
            step()
            steps += 1
        if cb is not None:
            cb.release_spikes()
            sup.close()
        assert eng.alloc.in_use == 0 and eng.alloc.refs == {}, "page leak"
        return _tokens([f for f in eng.finished
                        if f.reason == "complete"])

    got, base = run(spec_k, chaos), run(0, False)
    # the one cancel target may legitimately finish before the cancel
    # fires in one run but not the other (spec_k / retries shift how many
    # tokens a loop iteration emits); every request completed in BOTH
    # runs must be bit-identical, and no other request may go missing
    ctx = f"{name} spec_k={spec_k} cancel={cancel} chaos={chaos} seed={seed}"
    assert set(got) ^ set(base) <= ({1} if cancel else set()), (
        f"{ctx}: completed-request sets diverged beyond the cancel target")
    for r in set(got) & set(base):
        assert got[r] == base[r], f"{ctx}: rid {r} diverged"


# ---------------------------------------------- VMEM fallback regression --

def test_vmem_fallback_during_speculative_verify():
    """Regression: an oversized working set under `paged_impl="kernel"`
    with a 1-byte VMEM budget must degrade the speculative VERIFY program
    to the XLA path (warning once, counting every fallback) — and the
    degraded engine's streams stay bit-identical to an explicit
    `paged_impl="xla"` run.  The verify/draft programs are lru_cached by
    config, so a vocab unique to this test guarantees fresh traces."""
    from repro.kernels import ops

    def cfg_for(impl, budget):
        return ModelConfig(
            n_layers=2, d_model=64, n_heads=4, n_kv=2, d_ff=128, vocab=101,
            attn=AttnConfig(window=W, k=W, backend="mita_ref",
                            paged_impl=impl, vmem_budget=budget))

    specs = [(W, 6), (2 * W, 5)]
    ecfg = EngineConfig(n_slots=2, pages_per_slot=4, n_pages=12,
                        sample_device="fused", spec_k=2)

    def run(impl, budget):
        cfg = cfg_for(impl, budget)
        params = tfm.lm_init(jax.random.PRNGKey(0), cfg)
        eng = ServingEngine(params, cfg, ecfg,
                            backend=MiTABackend(params, cfg, ecfg))
        done = eng.run(_requests(cfg.vocab, specs))
        return _tokens(done), eng.stats()

    base = ops.paged_kernel_fallbacks()
    ops._PAGED_FALLBACK_WARNED = False
    with pytest.warns(RuntimeWarning, match="VMEM budget"):
        got, st = run("kernel", 1)
    assert ops.paged_kernel_fallbacks() > base, "fallback not counted"
    assert st["paged_kernel_fallbacks"] >= 1, \
        "backend stats missed the fallback delta"
    want, st_xla = run("xla", 0)
    assert got == want, "degraded kernel path diverged from explicit XLA"
    assert st_xla["paged_kernel_fallbacks"] == 0
