"""Kernel ↔ oracle parity for the routed-expert branch and the fused
paged-decode kernel.

`kernels/mita_expert_attn.py` (interpret=True on CPU) against the
`core/mita.py` routed branch, on exactly the cases the static-shape kernel
can get wrong: causal window masking, k wider than early window ends
(padded expert tiles), GQA group-shared routing, and pathological expert
load skew (a sorted query block spanning one expert vs many).

`kernels/mita_paged_attn.py` (interpret mode) against the XLA gather path
of `core/mita_decode.mita_paged_decode_step` (``paged_impl="xla"``), on
the cases the page walk can get wrong: randomized page permutations,
ragged per-slot progress, inactive slots, and the scratch-row append."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import mita as mref
from repro.core import mita_decode as mdec
from repro.core import mita_sparse as msp
from repro.core.mita import MiTAConfig, mita_attention
from repro.core.mita_sparse import mita_attention_sparse
from repro.kernels import ops

RNG = jax.random.PRNGKey(11)


def _qkv(b=1, h=2, n=128, d=16, key=RNG):
    return tuple(jax.random.normal(k, (b, h, n, d))
                 for k in jax.random.split(key, 3))


def test_routed_branch_kernel_vs_oracle_direct():
    """The kernel-backed sorted routed branch (expert_span=0 dispatches to
    `mita_expert_attention`) against `core.mita._routed_partial`, compared
    as normalized partials so no other branch can mask a mismatch."""
    q, k, v = _qkv(n=128)
    cfg = MiTAConfig(m=8, k=16, s=1, causal=True)
    q_lm = mref.extract_landmarks(q, cfg)
    s_kv = mref.landmark_scores(k, q_lm, cfg)
    r = mref.routing_logits(q, q_lm, cfg)
    k_e, v_e, valid = mref.gather_topk(k, v, s_kv, cfg)

    ref = mref._routed_partial(q, k_e, v_e, valid, r, cfg)
    out = msp._routed_sorted(q, k_e, v_e, valid, r, cfg, block_q=32,
                             expert_span=0)   # 0 -> Pallas kernel path

    act = np.asarray(ref.l) > 0
    assert np.array_equal(act, np.asarray(out.l) > 0)
    on = np.asarray(out.o, np.float32) / np.maximum(
        np.asarray(out.l)[..., None], 1e-30)
    rn = np.asarray(ref.o, np.float32) / np.maximum(
        np.asarray(ref.l)[..., None], 1e-30)
    np.testing.assert_allclose(on * act[..., None], rn * act[..., None],
                               atol=3e-5)
    np.testing.assert_allclose(np.asarray(out.m) * act,
                               np.asarray(ref.m) * act, atol=3e-5)


@pytest.mark.parametrize("s", [1, 2])
def test_pallas_causal_k_exceeds_window_end(s):
    """k > early window ends: the first windows contribute fewer than k
    valid rows, so the expert tiles carry causal padding the kernel must
    mask (NEG_INF bias lanes), not attend."""
    q, k, v = _qkv(n=128)
    cfg = MiTAConfig(m=8, k=32, s=s, causal=True)   # window = 16 < k = 32
    ref = mita_attention(q, k, v, cfg)
    out = mita_attention_sparse(q, k, v, cfg, impl="pallas", block_q=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)


def test_pallas_gqa_route_per_group():
    """route_per_group: ONE routing decision per KV group, shared by all G
    query heads — the kernel sees a broadcast-1 routing lead dim."""
    b, hkv, g, n, d = 2, 2, 4, 128, 16
    q = jax.random.normal(RNG, (b, hkv, g, n, d))
    k, v = (jax.random.normal(kk, (b, hkv, 1, n, d))
            for kk in jax.random.split(RNG, 2))
    q_lm = jnp.mean(q, axis=2, keepdims=True)
    cfg = MiTAConfig(m=8, k=16, causal=True, route_per_group=True)
    ref = mita_attention(q, k, v, cfg, q_landmarks=q_lm)
    out = mita_attention_sparse(q, k, v, cfg, impl="pallas", block_q=32,
                                q_landmarks=q_lm)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)


def test_pallas_uneven_expert_load():
    """Pathological skew: all queries share a dominant direction, so nearly
    every sub-query routes to the same expert.  A sorted query block then
    walks a single expert tile (dynamic fori_loop lower==upper) — the
    degenerate case of the kernel's expert-range walk."""
    b, h, n, d = 1, 2, 128, 16
    ks = jax.random.split(RNG, 4)
    base = jax.random.normal(ks[0], (d,))
    q = base + 0.05 * jax.random.normal(ks[1], (b, h, n, d))
    q = q.at[..., :16, :].multiply(5.0)   # window 0's landmark dominates
    k = base + 0.05 * jax.random.normal(ks[2], (b, h, n, d))
    v = jax.random.normal(ks[3], (b, h, n, d))
    cfg = MiTAConfig(m=8, k=16, s=1, causal=False)
    ref = mita_attention(q, k, v, cfg)
    out = mita_attention_sparse(q, k, v, cfg, impl="pallas", block_q=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=5e-5, rtol=1e-4)
    # the skew is real: >90% of queries on one expert
    r = mref.routing_logits(q, mref.extract_landmarks(q, cfg), cfg)
    top = np.asarray(jnp.argmax(r, axis=-1))
    _, counts = np.unique(top, return_counts=True)
    assert counts.max() > 0.9 * top.size


def test_expert_kernel_pads_ragged_ns():
    """NS not divisible by block_q: the kernel wrapper pads the sorted
    sub-queries with the inactive assignment id and slices the outputs —
    the caller-side divisibility constraint is gone (the span path keeps
    it; impl='pallas' must not)."""
    q, k, v = _qkv(n=120)            # n*s = 120, block_q = 32 -> pad to 128
    cfg = MiTAConfig(m=8, k=16, s=1, causal=False)
    ref = mita_attention(q, k, v, cfg)
    out = mita_attention_sparse(q, k, v, cfg, impl="pallas", block_q=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)
    with pytest.raises(ValueError, match="block_q"):
        mita_attention_sparse(q, k, v, cfg, impl="sorted", block_q=32)


def test_pallas_all_experts_invalid_early_rows():
    """Causal + tiny first window where even expert 0's tile is partially
    invalid; queries before the first window end have NO routable expert —
    their routed partial must be empty (l == 0), never NaN."""
    q, k, v = _qkv(n=64)
    cfg = MiTAConfig(m=8, k=16, s=1, causal=True)    # window = 8 < k
    q_lm = mref.extract_landmarks(q, cfg)
    s_kv = mref.landmark_scores(k, q_lm, cfg)
    r = mref.routing_logits(q, q_lm, cfg)
    k_e, v_e, valid = mref.gather_topk(k, v, s_kv, cfg)
    out = msp._routed_sorted(q, k_e, v_e, valid, r, cfg, block_q=32,
                             expert_span=0)
    l = np.asarray(out.l)
    # expert 0 becomes available at t = w-1 ((i+1)*w <= t+1); before that
    # a query has no routable expert
    assert np.all(l[..., : 7] == 0.0)
    assert np.isfinite(np.asarray(out.o)).all()
    ref = mref._routed_partial(q, k_e, v_e, valid, r, cfg)
    assert np.array_equal(l > 0, np.asarray(ref.l) > 0)


# ------------------------------------------------- fused paged-decode kernel --

W, K = 8, 8


def _paged_pair(s_route=1, external=True, impl="kernel"):
    cfg_x = mdec.DecodeConfig(window=W, k=K, s=s_route, paged_impl="xla",
                              external_finalize=external)
    return cfg_x, dataclasses.replace(cfg_x, paged_impl=impl)


def _drive(cfg_x, cfg_k, offs, n_steps, seed=3, hkv=2, g=2, d=16,
           record=None):
    """Step the XLA oracle and the kernel side by side over a shuffled page
    pool with per-slot staggered activity (slot s joins at step offs[s]);
    assert outputs AND pools match every step (the pools pin the fused
    scratch-row append), that inactive slots output exact zeros, and that
    a step writes no pool row but the active slots' append rows and the
    scratch row.  ``record`` collects the kernel's outputs."""
    b = len(offs)
    key = jax.random.PRNGKey(seed)
    q = jax.random.normal(key, (b, hkv, g, n_steps, d))
    k, v = (jax.random.normal(kk, (b, hkv, n_steps, d))
            for kk in jax.random.split(key, 2))
    m = (n_steps + W - 1) // W
    n_pages = b * m + 2
    table = np.random.default_rng(seed).permutation(n_pages)[: b * m]
    page_table = jnp.asarray(table.reshape(b, m), jnp.int32)
    st_x = mdec.init_paged_state(hkv, d, n_pages, b, m, cfg_x, jnp.float32)
    st_k = mdec.init_paged_state(hkv, d, n_pages, b, m, cfg_k, jnp.float32)
    step_x = jax.jit(lambda s, *a: mdec.mita_paged_decode_step(s, *a, cfg_x))
    step_k = jax.jit(lambda s, *a: mdec.mita_paged_decode_step(s, *a, cfg_k))
    fin = jax.jit(lambda s, *a: mdec.mita_paged_finalize(s, *a, cfg_x))
    t = np.zeros(b, np.int32)
    m_done = np.zeros(b, np.int32)
    for i in range(n_steps):
        act = np.array([offs[s] <= i for s in range(b)])
        if cfg_x.external_finalize:
            due = act & (t % W == 0) & (t // W > m_done)
            if due.any():
                td, dd = jnp.asarray(t), jnp.asarray(due)
                st_x = fin(st_x, page_table, td, dd)
                st_k = fin(st_k, page_table, td, dd)
                m_done = np.where(due, t // W, m_done)
        qi = jnp.stack([q[s, :, :, (i - offs[s]) % n_steps] for s in range(b)])
        ki = jnp.stack([k[s, :, (i - offs[s]) % n_steps] for s in range(b)])
        vi = jnp.stack([v[s, :, (i - offs[s]) % n_steps] for s in range(b)])
        td, ad = jnp.asarray(t), jnp.asarray(act)
        before = np.asarray(st_k.k_pool)
        o_x, st_x = step_x(st_x, qi, ki, vi, page_table, td, ad)
        o_k, st_k = step_k(st_k, qi, ki, vi, page_table, td, ad)
        np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_x),
                                   atol=2e-5, err_msg=f"step {i}")
        assert not np.asarray(o_k)[~act].any(), f"step {i}"
        if record is not None:
            record.append(np.asarray(o_k))
        appends = {int(page_table[s, t[s] // W]) * W + int(t[s]) % W
                   for s in range(b) if act[s]}
        written = np.flatnonzero(
            (np.asarray(st_k.k_pool) != before).any(axis=(1, 2)))
        assert set(written) <= appends | {before.shape[0] - 1}, (
            f"step {i}: pool rows {sorted(set(written) - appends)} written")
        np.testing.assert_array_equal(np.asarray(st_k.k_pool),
                                      np.asarray(st_x.k_pool),
                                      err_msg=f"k_pool step {i}")
        np.testing.assert_array_equal(np.asarray(st_k.v_pool),
                                      np.asarray(st_x.v_pool),
                                      err_msg=f"v_pool step {i}")
        t = t + act
    return st_x, st_k


@pytest.mark.parametrize("s_route,external,g", [
    pytest.param(1, True, 2, id="1-True"),
    pytest.param(2, True, 2, id="2-True"),
    pytest.param(1, False, 2, id="1-False"),
    pytest.param(1, True, 8, id="1-True-g8"),
    pytest.param(2, True, 8, id="2-True-g8")])
def test_paged_kernel_matches_xla_staggered(s_route, external, g):
    """Kernel vs XLA gather path over shuffled pages, ragged per-slot t
    (slots join at different steps), inactive slots, inline + external
    finalize, and multi-expert routing.  Pools are compared bit-exactly —
    the kernel's fused append (external mode) must write exactly the rows
    the XLA scatter writes, scratch row included.  The g8 cases give each
    KV head 8 query heads, each routing on its own (one expert tile per
    head and route, the next one gathered while one is computed), with a
    slot that stays inactive (no walk, zero output, appends only to the
    scratch row) and a slot that joins late and has no visible landmark
    for its first window (no routed walk, local and shared branches
    only)."""
    cfg_x, cfg_k = _paged_pair(s_route=s_route, external=external)
    offs = [0, 5, 11] if g == 2 else [0, 5, 17, 24]
    _drive(cfg_x, cfg_k, offs=offs, n_steps=24, g=g)


def test_paged_kernel_scratch_row_append():
    """An inactive slot's fused append lands in the scratch row and ONLY
    the scratch row — no owned page of any other slot changes."""
    cfg_x, cfg_k = _paged_pair()
    b, hkv, g, d, m = 2, 2, 1, 16, 2
    n_pages = b * m
    page_table = jnp.asarray(np.arange(n_pages).reshape(b, m), jnp.int32)
    st = mdec.init_paged_state(hkv, d, n_pages, b, m, cfg_k, jnp.float32)
    key = jax.random.PRNGKey(0)
    qi = jax.random.normal(key, (b, hkv, g, d))
    ki, vi = (jax.random.normal(kk, (b, hkv, d))
              for kk in jax.random.split(key, 2))
    act = jnp.asarray([True, False])
    t = jnp.asarray([3, 0], jnp.int32)
    before = np.asarray(st.k_pool)
    _, st2 = jax.jit(lambda s, *a: mdec.mita_paged_decode_step(
        s, *a, cfg_k))(st, qi, ki, vi, page_table, t, act)
    after = np.asarray(st2.k_pool)
    scratch = after.shape[0] - 1
    np.testing.assert_array_equal(after[scratch], np.asarray(ki)[1])
    # slot 0 wrote its own page row; every other non-scratch row unchanged
    row0 = int(page_table[0, 0]) * W + 3
    np.testing.assert_array_equal(after[row0], np.asarray(ki)[0])
    mask = np.ones(after.shape[0], bool)
    mask[[row0, scratch]] = False
    np.testing.assert_array_equal(after[mask], before[mask])


def test_paged_kernel_shared_prefix_pages_append_isolation():
    """Two slots whose page tables alias the same prefix pages (the prefix
    cache's read-sharing): a decode step writes ONLY each slot's exclusive
    append row, in both the fused kernel and the XLA path — a shared page
    never takes the in-place append, so read-only sharing needs no copy."""
    cfg_x, cfg_k = _paged_pair()
    b, hkv, g, d, m = 2, 2, 1, 16, 3
    n_pages = 5
    table = np.asarray([[0, 1, 2], [0, 1, 3]], np.int32)  # pages 0,1 shared
    page_table = jnp.asarray(table)
    kq, kk, kv, kp = jax.random.split(jax.random.PRNGKey(7), 4)
    qi = jax.random.normal(kq, (b, hkv, g, d))
    ki = jax.random.normal(kk, (b, hkv, d))
    vi = jax.random.normal(kv, (b, hkv, d))
    pool = jax.random.normal(kp, (n_pages * W + 1, hkv, d))
    t = jnp.asarray([2 * W + 1, 2 * W + 3], jnp.int32)
    act = jnp.asarray([True, True])
    states = {}
    for name, cfg in (("kernel", cfg_k), ("xla", cfg_x)):
        st = mdec.init_paged_state(hkv, d, n_pages, b, m, cfg, jnp.float32)
        st = st._replace(k_pool=pool, v_pool=pool + 1.0)
        out, st2 = jax.jit(lambda s, *a, c=cfg: mdec.mita_paged_decode_step(
            s, *a, c))(st, qi, ki, vi, page_table, t, act)
        states[name] = (np.asarray(out), st2)
    np.testing.assert_allclose(states["kernel"][0], states["xla"][0],
                               atol=2e-5)
    rows = [int(table[0, 2]) * W + 1, int(table[1, 2]) * W + 3]
    before_k, before_v = np.asarray(pool), np.asarray(pool) + 1.0
    for name, st2 in ((n, s) for n, (_, s) in states.items()):
        for pname, after, src, base in (
                ("k_pool", np.asarray(st2.k_pool), np.asarray(ki), before_k),
                ("v_pool", np.asarray(st2.v_pool), np.asarray(vi), before_v)):
            np.testing.assert_array_equal(after[rows[0]], src[0],
                                          err_msg=f"{name} {pname} slot0")
            np.testing.assert_array_equal(after[rows[1]], src[1],
                                          err_msg=f"{name} {pname} slot1")
            mask = np.ones(after.shape[0], bool)
            mask[rows] = False
            np.testing.assert_array_equal(
                after[mask], base[mask],
                err_msg=f"{name} {pname} shared pages mutated")


def test_paged_kernel_vmem_budget_dispatch(monkeypatch):
    """Dispatch flips to the XLA fallback when the VMEM budget shrinks —
    via the DecodeConfig field and via REPRO_VMEM_BUDGET_BYTES — and the
    step stays correct either way (it IS the fallback)."""
    shape = dict(window=W, m=4, k_width=K, g=2, d=16, itemsize=4)
    assert ops.use_paged_kernel("kernel", **shape)
    assert not ops.use_paged_kernel("kernel", **shape, budget=64)
    assert not ops.use_paged_kernel("xla", **shape)
    monkeypatch.setenv("REPRO_VMEM_BUDGET_BYTES", "64")
    assert ops.vmem_budget_bytes() == 64
    assert not ops.use_paged_kernel("kernel", **shape)
    monkeypatch.delenv("REPRO_VMEM_BUDGET_BYTES")
    # a "kernel" config whose working set exceeds the budget must still
    # produce oracle-exact results (it silently runs the fallback)
    cfg_x, cfg_tiny = _paged_pair()
    cfg_tiny = dataclasses.replace(cfg_tiny, vmem_budget=64)
    _drive(cfg_x, cfg_tiny, offs=[0, 0, 0], n_steps=4)


def test_gather_pages_owned_redirects_to_scratch():
    """`gather_pages(owned=...)`: table entries past the owned prefix read
    the scratch row, not whatever (other requests') pages the unused
    entries happen to name."""
    hkv, d, w = 2, 4, 4
    pool = jnp.arange(9 * hkv * d, dtype=jnp.float32).reshape(9, hkv, d)
    page_ids = jnp.asarray([[0, 1], [1, 0]], jnp.int32)   # slot 1 unused
    out = ops.gather_pages(pool, page_ids, w, d,
                           owned=jnp.asarray([1, 2], jnp.int32))
    ref = np.asarray(pool)
    # slot 0: first page real, second page -> scratch row replicated
    np.testing.assert_array_equal(np.asarray(out)[0, :w], ref[0:w])
    np.testing.assert_array_equal(np.asarray(out)[0, w:],
                                  np.broadcast_to(ref[8], (w, hkv, d)))
    # slot 1 owns both pages: untouched
    np.testing.assert_array_equal(
        np.asarray(out)[1], np.concatenate([ref[4:8], ref[0:4]]))


def test_pool_row_helpers_hide_lane_padding():
    """The pool row layout (`ops.pool_lanes`) stays inside `kernels.ops`:
    `scatter_pool_rows` writes ``[..., Hkv, d]`` rows into lane-padded
    pools with zero pad lanes, and both gathers return exactly the head
    dim."""
    hkv, d, lanes, w = 2, 4, 8, 4
    pool = jnp.full((9, hkv, lanes), 7.0, jnp.float32)
    rows = jnp.asarray([[1, 5], [2, 6]], jnp.int32)
    new = jnp.arange(2 * 2 * hkv * d, dtype=jnp.bfloat16).reshape(
        2, 2, hkv, d)
    pool = ops.scatter_pool_rows(pool, rows, new)
    assert pool.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(pool)[[1, 5, 2, 6], :, :d],
                                  np.asarray(new, np.float32).reshape(
                                      4, hkv, d))
    assert not np.asarray(pool)[[1, 5, 2, 6], :, d:].any()
    got = ops.gather_pool_rows(pool, jnp.asarray([[[5, 1]] * hkv]), d)
    assert got.shape == (1, hkv, 2, d)
    np.testing.assert_array_equal(np.asarray(got)[0, :, 0],
                                  np.asarray(pool)[5, :, :d])
    pages = ops.gather_pages(pool, jnp.asarray([[0, 1]], jnp.int32), w, d)
    assert pages.shape == (1, 2 * w, hkv, d)
    np.testing.assert_array_equal(np.asarray(pages)[0],
                                  np.asarray(pool)[:2 * w, :, :d])


# ------------------------------------------------ fused chunk-prefill kernel --


def _chunk_pair(s_route=1, external=True):
    cfg_x = mdec.DecodeConfig(window=W, k=K, s=s_route, prefill_impl="xla",
                              external_finalize=external)
    return cfg_x, dataclasses.replace(cfg_x, prefill_impl="kernel")


def _drive_chunks(cfg_x, cfg_k, n_trains, n_totals, chunk, m_slot=4,
                  hkv=2, g=2, d=16, stagger=True, seed=5):
    """Chunk-prefill the kernel and the XLA oracle side by side over a
    shuffled page pool; slots advance on alternating steps (ragged resume
    points + inactive rows in every dispatch).  State tensors and owned
    pages are compared BIT-exactly after every dispatch; outputs allclose
    on valid positions."""
    s_n = len(n_totals)
    key = jax.random.PRNGKey(seed)
    n_pages = s_n * m_slot + 2
    table = np.random.default_rng(seed).permutation(n_pages)[: s_n * m_slot]
    pt = jnp.asarray(table.reshape(s_n, m_slot), jnp.int32)
    nmax = max(n_totals)
    q = jax.random.normal(key, (s_n, hkv, g, nmax, d))
    k, v = (jax.random.normal(kk, (s_n, hkv, nmax, d))
            for kk in jax.random.split(key, 2))
    st_x = mdec.init_paged_state(hkv, d, n_pages, s_n, m_slot, cfg_x,
                                 jnp.float32)
    st_k = mdec.init_paged_state(hkv, d, n_pages, s_n, m_slot, cfg_k,
                                 jnp.float32)
    step = jax.jit(mdec.mita_batched_chunk_prefill, static_argnames="cfg")
    done = np.zeros(s_n, np.int32)
    it = 0
    while (done < np.asarray(n_totals)).any():
        act = done < np.asarray(n_totals)
        if stagger and s_n > 1:
            act = act & (np.arange(s_n) % 2 == it % 2)
        it += 1
        if not act.any():
            continue
        nv = np.where(act, np.minimum(chunk, np.asarray(n_totals) - done), 0)
        qc = np.zeros((s_n, hkv, g, chunk, d), np.float32)
        kc = np.zeros((s_n, hkv, chunk, d), np.float32)
        vc = np.zeros((s_n, hkv, chunk, d), np.float32)
        for s in range(s_n):
            if act[s]:
                sl = slice(done[s], done[s] + nv[s])
                qc[s, :, :, : nv[s]] = np.asarray(q[s, :, :, sl])
                kc[s, :, : nv[s]] = np.asarray(k[s, :, sl])
                vc[s, :, : nv[s]] = np.asarray(v[s, :, sl])
        args = (jnp.asarray(qc), jnp.asarray(kc), jnp.asarray(vc), pt,
                jnp.arange(s_n, dtype=jnp.int32), jnp.asarray(done),
                jnp.asarray(nv), jnp.asarray(n_trains, jnp.int32),
                jnp.asarray(act))
        o_x, st_x = step(st_x, *args, cfg=cfg_x)
        o_k, st_k = step(st_k, *args, cfg=cfg_k)
        o_x, o_k = np.asarray(o_x), np.asarray(o_k)
        for s in range(s_n):
            np.testing.assert_allclose(
                o_k[s][:, :, : nv[s]], o_x[s][:, :, : nv[s]], atol=2e-5,
                err_msg=f"out slot {s} step {it}")
        for f in ("lm_q", "lm_v", "expert_idx", "expert_valid", "q_sum",
                  "pre_lm_q", "pre_q_sum"):
            np.testing.assert_array_equal(
                np.asarray(getattr(st_k, f)), np.asarray(getattr(st_x, f)),
                err_msg=f"{f} step {it}")
        # owned pages bit-exact (the trailing scratch row soaks up write
        # order differences between the flat scatter and the DMA loop)
        np.testing.assert_array_equal(np.asarray(st_k.k_pool)[:-1],
                                      np.asarray(st_x.k_pool)[:-1],
                                      err_msg=f"k_pool step {it}")
        np.testing.assert_array_equal(np.asarray(st_k.v_pool)[:-1],
                                      np.asarray(st_x.v_pool)[:-1],
                                      err_msg=f"v_pool step {it}")
        done = done + nv
    return st_x, st_k


@pytest.mark.parametrize("s_route,external", [(1, True), (2, True),
                                              (1, False)])
def test_chunk_kernel_matches_xla_ragged(s_route, external):
    """Kernel vs XLA over shuffled pages, ragged resume points (slots
    advance on alternating dispatches, so every dispatch mixes active and
    inactive rows), preemption-recompute rows (n_total > n_train replicates
    decode-time landmark availability), multi-expert routing, and both
    finalize modes.  All state — landmarks, expert rows, both q_sum
    systems, owned pages — is compared bit-exactly after every dispatch."""
    _drive_chunks(*_chunk_pair(s_route=s_route, external=external),
                  n_trains=[32, 16, 20], n_totals=[32, 24, 28], chunk=8)


def test_chunk_kernel_nonaligned_heads():
    """Non-window-aligned prompts (the n//m landmark-ends quirk: w' = 10
    for n = 20, w' = n for single-landmark prompts) through the kernel,
    bit-identical to the XLA oracle — including a chunk length SHORTER
    than w', which forces the eager landmark-query commit to cross a
    dispatch before its score context exists."""
    _drive_chunks(*_chunk_pair(), n_trains=[20, 12], n_totals=[20, 12],
                  chunk=8)


def test_chunk_kernel_inactive_slots_untouched():
    """A dispatch with an inactive row leaves that slot's landmark/expert/
    q_sum state and every owned page bit-identical (checked every dispatch
    by the driver since slots alternate), and a fully-prefilled batch
    matches the single-slot oracle's final state."""
    cfg_x, cfg_k = _chunk_pair()
    st_x, st_k = _drive_chunks(cfg_x, cfg_k, n_trains=[16, 16],
                               n_totals=[16, 16], chunk=16)
    # cross-check one slot against the single-slot chunk op
    key = jax.random.PRNGKey(5)
    q = jax.random.normal(key, (2, 2, 2, 16, 16))
    k, v = (jax.random.normal(kk, (2, 2, 16, 16))
            for kk in jax.random.split(key, 2))
    n_pages = 2 * 4 + 2
    table = np.random.default_rng(5).permutation(n_pages)[: 2 * 4]
    pt = jnp.asarray(table.reshape(2, 4), jnp.int32)
    st1 = mdec.init_paged_state(2, 16, n_pages, 2, 4, cfg_x, jnp.float32)
    _, st1 = jax.jit(mdec.mita_chunk_prefill, static_argnames="cfg")(
        st1, q[0], k[0], v[0], pt[0], 0, 0, 16, 16, cfg_x)
    np.testing.assert_allclose(np.asarray(st_k.lm_q)[0],
                               np.asarray(st1.lm_q)[0], atol=2e-5)
    np.testing.assert_allclose(np.asarray(st_k.q_sum)[0],
                               np.asarray(st1.q_sum)[0], atol=2e-5)
    np.testing.assert_array_equal(np.asarray(st_k.expert_idx)[0],
                                  np.asarray(st1.expert_idx)[0])


def test_chunk_kernel_recompute_round_trip():
    """Preemption recompute at the core level: build a state by chunked
    prefill of prompt-then-generated (n_train < n_total), rebuild it from
    scratch in one go, and require the kernel and oracle to agree
    bit-exactly on both builds AND the two builds to agree with each other
    (recompute-from-prompt is deterministic)."""
    cfg_x, cfg_k = _chunk_pair()
    st_a, _ = _drive_chunks(cfg_x, cfg_k, n_trains=[16], n_totals=[32],
                            chunk=8, stagger=False)
    st_b, _ = _drive_chunks(cfg_x, cfg_k, n_trains=[16], n_totals=[32],
                            chunk=16, stagger=False)
    for f in ("lm_q", "lm_v", "expert_idx", "expert_valid", "q_sum"):
        np.testing.assert_allclose(
            np.asarray(getattr(st_a, f)), np.asarray(getattr(st_b, f)),
            atol=2e-5, err_msg=f"{f} chunk-size invariance")


def test_prefill_impl_dispatch(monkeypatch):
    """`use_prefill_kernel`: tri-state impl + VMEM budget + the
    REPRO_PREFILL_IMPL env override flip dispatch without touching
    numerics (the XLA path IS the fallback)."""
    shape = dict(nc=16, window=W, m=4, k_width=K, g=2, d=16, itemsize=4)
    assert ops.use_prefill_kernel("kernel", **shape)
    assert not ops.use_prefill_kernel("kernel", **shape, budget=64)
    assert not ops.use_prefill_kernel("xla", **shape)
    with pytest.raises(ValueError, match="prefill impl"):
        ops.use_prefill_kernel("bogus", **shape)
    monkeypatch.setenv("REPRO_PREFILL_IMPL", "xla")
    assert not ops.use_prefill_kernel("kernel", **shape)
    monkeypatch.setenv("REPRO_PREFILL_IMPL", "kernel")
    assert ops.use_prefill_kernel("xla", **shape)
    monkeypatch.delenv("REPRO_PREFILL_IMPL")
    # an oversized "kernel" config silently runs the oracle
    cfg_x, cfg_k = _chunk_pair()
    cfg_tiny = dataclasses.replace(cfg_k, vmem_budget=64)
    _drive_chunks(cfg_x, cfg_tiny, n_trains=[16], n_totals=[16], chunk=16)


@pytest.mark.parametrize("qb", [4, 2, 1, None])
def test_chunk_kernel_forced_tile_sweep(qb, monkeypatch):
    """VMEM-budget-driven tiling flips: REPRO_VMEM_BUDGET_BYTES values
    computed from the estimator force every local-branch tile size the
    selector can produce (q_block = nw, nw/2, 1) and, below the smallest
    tile, the counted XLA fallback — parity must be bit-exact at every
    tile shape (the tiled kernel merges no partials across tiles, so no
    tolerance loosening is allowed)."""
    shape = dict(nc=32, window=W, m=4, k_width=K, g=2, d=16, itemsize=4)
    need = {b: ops.chunk_prefill_vmem_bytes(**shape, q_block=b)
            for b in (4, 2, 1)}
    assert need[1] < need[2] < need[4]
    budget = need[qb] if qb else need[1] - 1
    # the env override reaches the selector (budget=0 reads it)...
    monkeypatch.setenv("REPRO_VMEM_BUDGET_BYTES", str(budget))
    assert ops.select_prefill_q_block(**shape) == qb
    monkeypatch.delenv("REPRO_VMEM_BUDGET_BYTES")
    # ...while the drive pins the budget via DecodeConfig so each swept
    # value is part of the static jit key (an env flip alone would reuse
    # the first parameterization's compiled trace and tile size)
    cfg_x, cfg_k = _chunk_pair()
    cfg_k = dataclasses.replace(cfg_k, vmem_budget=budget)
    with ops.scoped_fallback_counters() as fb:
        _drive_chunks(cfg_x, cfg_k, n_trains=[32, 32], n_totals=[32, 32],
                      chunk=32)
    if qb is None:
        assert fb["prefill"] >= 1      # counted, and still oracle-exact
    else:
        assert fb["prefill"] == 0


# ---------------------------------------------- fused paged-finalize kernel --


def _finalize_pair(s_route=1):
    cfg_x = mdec.DecodeConfig(window=W, k=K, s=s_route, finalize_impl="xla",
                              external_finalize=True)
    return cfg_x, dataclasses.replace(cfg_x, finalize_impl="kernel")


def _finalize_state(cfg, s_n=4, m_slot=4, hkv=2, d=16, seed=9):
    """A paged state with fully random pools, landmarks, and window-query
    accumulators over a SHUFFLED page table — nothing about the finalize
    may depend on pool layout beyond what the table names."""
    n_pages = s_n * m_slot + 2
    table = np.random.default_rng(seed).permutation(n_pages)[: s_n * m_slot]
    pt = jnp.asarray(table.reshape(s_n, m_slot), jnp.int32)
    st = mdec.init_paged_state(hkv, d, n_pages, s_n, m_slot, cfg,
                               jnp.float32)
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return st._replace(
        k_pool=jax.random.normal(ks[0], st.k_pool.shape, st.k_pool.dtype),
        v_pool=jax.random.normal(ks[1], st.v_pool.shape, st.v_pool.dtype),
        q_sum=jax.random.normal(ks[2], st.q_sum.shape, jnp.float32),
        lm_q=jax.random.normal(ks[3], st.lm_q.shape, st.lm_q.dtype),
        lm_v=jax.random.normal(ks[4], st.lm_v.shape, st.lm_v.dtype)), pt


_FIN_FIELDS = ("lm_q", "lm_v", "expert_idx", "expert_valid", "q_sum")


@pytest.mark.parametrize("t_new,due", [
    ((8, 16, 0, 29), (True, True, False, False)),
    ((32, 8, 24, 5), (True, True, True, False)),
])
def test_finalize_kernel_matches_xla(t_new, due):
    """Finalize kernel vs the `_paged_finalize` XLA oracle over a shuffled
    page table, ragged per-slot t (first/middle/last window ordinals),
    non-due and inactive (t = 0) slots: landmarks, expert rows, validity,
    and q_sum bit-exact; pools untouched."""
    cfg_x, cfg_k = _finalize_pair()
    st, pt = _finalize_state(cfg_x)
    td = jnp.asarray(t_new, jnp.int32)
    dd = jnp.asarray(due)
    fin = jax.jit(mdec.mita_paged_finalize, static_argnames="cfg")
    st_x = fin(st, pt, td, dd, cfg=cfg_x)
    st_k = fin(st, pt, td, dd, cfg=cfg_k)
    for f in _FIN_FIELDS:
        np.testing.assert_array_equal(np.asarray(getattr(st_k, f)),
                                      np.asarray(getattr(st_x, f)),
                                      err_msg=f)
    for f in ("k_pool", "v_pool"):
        np.testing.assert_array_equal(np.asarray(getattr(st_k, f)),
                                      np.asarray(getattr(st_x, f)),
                                      err_msg=f)
    # non-due rows pass through bit-exactly (q_sum zeroing is due-gated)
    for f in _FIN_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(st_k, f))[~np.asarray(due)],
            np.asarray(getattr(st, f))[~np.asarray(due)],
            err_msg=f"{f} non-due passthrough")


def test_finalize_kernel_in_decode_loop():
    """The finalize kernel inside the full external-finalize decode drive:
    the `_drive` loop re-runs with the KERNEL finalize on one side and the
    XLA finalize on the other (decode steps identical), pinning the
    integration point `_paged_finalize` dispatches through."""
    cfg_x = mdec.DecodeConfig(window=W, k=K, s=1, paged_impl="xla",
                              external_finalize=True, finalize_impl="xla")
    cfg_k = dataclasses.replace(cfg_x, finalize_impl="kernel")
    key = jax.random.PRNGKey(3)
    b, hkv, g, d, n_steps = 3, 2, 2, 16, 24
    q = jax.random.normal(key, (b, hkv, g, n_steps, d))
    k, v = (jax.random.normal(kk, (b, hkv, n_steps, d))
            for kk in jax.random.split(key, 2))
    m = (n_steps + W - 1) // W
    n_pages = b * m + 2
    table = np.random.default_rng(3).permutation(n_pages)[: b * m]
    pt = jnp.asarray(table.reshape(b, m), jnp.int32)
    st_x = mdec.init_paged_state(hkv, d, n_pages, b, m, cfg_x, jnp.float32)
    st_k = st_x
    step = jax.jit(lambda s, *a: mdec.mita_paged_decode_step(s, *a, cfg_x))
    fin = jax.jit(mdec.mita_paged_finalize, static_argnames="cfg")
    offs = [0, 5, 11]
    t = np.zeros(b, np.int32)
    m_done = np.zeros(b, np.int32)
    for i in range(n_steps):
        act = np.array([offs[s] <= i for s in range(b)])
        due = act & (t % W == 0) & (t // W > m_done)
        if due.any():
            td, dd = jnp.asarray(t), jnp.asarray(due)
            st_x = fin(st_x, pt, td, dd, cfg=cfg_x)
            st_k = fin(st_k, pt, td, dd, cfg=cfg_k)
            for f in _FIN_FIELDS:
                np.testing.assert_array_equal(
                    np.asarray(getattr(st_k, f)),
                    np.asarray(getattr(st_x, f)), err_msg=f"{f} step {i}")
            m_done = np.where(due, t // W, m_done)
        qi = jnp.stack([q[s, :, :, (i - offs[s]) % n_steps]
                        for s in range(b)])
        ki = jnp.stack([k[s, :, (i - offs[s]) % n_steps] for s in range(b)])
        vi = jnp.stack([v[s, :, (i - offs[s]) % n_steps] for s in range(b)])
        td, ad = jnp.asarray(t), jnp.asarray(act)
        o_x, st_x = step(st_x, qi, ki, vi, pt, td, ad)
        o_k, st_k = step(st_k, qi, ki, vi, pt, td, ad)
        np.testing.assert_array_equal(np.asarray(o_k), np.asarray(o_x),
                                      err_msg=f"decode out step {i}")
        t = t + act


def test_finalize_impl_dispatch(monkeypatch):
    """`use_finalize_kernel`: tri-state impl + VMEM budget + the
    REPRO_FINALIZE_IMPL env override flip dispatch without touching
    numerics (the XLA path IS the fallback)."""
    shape = dict(window=W, m=4, k_width=K, d=16, itemsize=4)
    assert ops.use_finalize_kernel("kernel", **shape)
    assert not ops.use_finalize_kernel("kernel", **shape, budget=64)
    assert not ops.use_finalize_kernel("xla", **shape)
    with pytest.raises(ValueError, match="finalize impl"):
        ops.use_finalize_kernel("bogus", **shape)
    monkeypatch.setenv("REPRO_FINALIZE_IMPL", "xla")
    assert not ops.use_finalize_kernel("kernel", **shape)
    monkeypatch.setenv("REPRO_FINALIZE_IMPL", "kernel")
    assert ops.use_finalize_kernel("xla", **shape)
    monkeypatch.delenv("REPRO_FINALIZE_IMPL")
    # an oversized "kernel" config silently runs the oracle, counted
    cfg_x, cfg_k = _finalize_pair()
    cfg_tiny = dataclasses.replace(cfg_k, vmem_budget=64)
    st, pt = _finalize_state(cfg_x)
    td = jnp.asarray([8, 16, 0, 29], jnp.int32)
    dd = jnp.asarray([True, True, False, False])
    fin = jax.jit(mdec.mita_paged_finalize, static_argnames="cfg")
    with ops.scoped_fallback_counters() as fb:
        st_t = fin(st, pt, td, dd, cfg=cfg_tiny)
    assert fb["finalize"] >= 1 and fb["prefill"] == 0
    st_x = fin(st, pt, td, dd, cfg=cfg_x)
    for f in _FIN_FIELDS:
        np.testing.assert_array_equal(np.asarray(getattr(st_t, f)),
                                      np.asarray(getattr(st_x, f)),
                                      err_msg=f)


def _chunk_once(cfg, lanes_pad, seed=6, s_n=3, m_slot=4, hkv=2, g=2, d=16,
                chunk=16):
    """One batched chunk-prefill dispatch from a fresh state (with KV pool
    rows padded with junk to ``lanes_pad`` lanes when given)."""
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 3)
    n_pages = s_n * m_slot + 2
    table = np.random.default_rng(seed).permutation(n_pages)[: s_n * m_slot]
    st = mdec.init_paged_state(hkv, d, n_pages, s_n, m_slot, cfg,
                               jnp.float32)
    if lanes_pad:
        st = _pad_lanes(st, lanes_pad)
    return jax.jit(mdec.mita_batched_chunk_prefill, static_argnames="cfg")(
        st, jax.random.normal(ks[0], (s_n, hkv, g, chunk, d)),
        jax.random.normal(ks[1], (s_n, hkv, chunk, d)),
        jax.random.normal(ks[2], (s_n, hkv, chunk, d)),
        jnp.asarray(table.reshape(s_n, m_slot), jnp.int32),
        jnp.arange(s_n, dtype=jnp.int32), jnp.zeros(s_n, jnp.int32),
        jnp.asarray([16, 12, 16], jnp.int32),
        jnp.asarray([16, 12, 20], jnp.int32),
        jnp.asarray([True, True, False]), cfg=cfg)


def _pad_lanes(st, lanes, junk=1e3):
    """``st`` with its pool rows padded to ``lanes`` lanes of junk."""
    pad = ((0, 0), (0, 0), (0, lanes - st.k_pool.shape[-1]))
    return st._replace(k_pool=jnp.pad(st.k_pool, pad, constant_values=junk),
                       v_pool=jnp.pad(st.v_pool, pad, constant_values=junk))


@pytest.mark.parametrize("kind", ["decode", "finalize", "chunk"])
def test_lane_padded_pools(kind, monkeypatch):
    """On the TPU the pools' head rows are padded to whole 128-lane tiles
    (`ops.pool_lanes`); the kernels and the XLA paths must read only the
    head dim.  Forced here on the CPU: the decode drive keeps its
    kernel-vs-XLA parity with padded pools, and finalize / chunk prefill
    give their unpadded results, on both paths, with junk in the pad
    lanes (a write zeroes them)."""
    if kind == "decode":
        monkeypatch.setattr(ops, "pool_lanes", lambda d: 128)
        _, st_k = _drive(*_paged_pair(s_route=2, external=True),
                         offs=[0, 5, 11], n_steps=24)
        assert st_k.k_pool.shape[-1] == 128
        assert not np.asarray(st_k.k_pool)[..., 16:].any()
        return
    if kind == "finalize":
        st, pt = _finalize_state(_finalize_pair()[0])
        args = (pt, jnp.asarray([8, 16, 0, 29], jnp.int32),
                jnp.asarray([True, True, False, True]))
        fn = jax.jit(mdec.mita_paged_finalize, static_argnames="cfg")
        runs = [(fn(st, *args, cfg=cfg), fn(_pad_lanes(st, 128), *args,
                                            cfg=cfg))
                for cfg in _finalize_pair()]
        fields = _FIN_FIELDS
    else:
        runs = [(_chunk_once(cfg, 0), _chunk_once(cfg, 128))
                for cfg in _chunk_pair()]
        for (o0, _), (o1, _) in runs:
            np.testing.assert_allclose(np.asarray(o1), np.asarray(o0),
                                       atol=1e-6)
        runs = [(r0[1], r1[1]) for r0, r1 in runs]
        fields = _FIN_FIELDS + ("pre_lm_q", "pre_q_sum")
    for s0, s1 in runs:
        for f in fields:
            np.testing.assert_allclose(np.asarray(getattr(s1, f)),
                                       np.asarray(getattr(s0, f)),
                                       atol=1e-6, err_msg=f)
        np.testing.assert_array_equal(np.asarray(s1.k_pool)[..., :16],
                                      np.asarray(s0.k_pool))


def test_kernels_interpret_only_on_cpu(monkeypatch):
    """Kernels compile on the TPU, interpret on the CPU backend, and refuse
    every other backend instead of silently interpreting there."""
    assert ops.kernel_interpret() is True            # the CPU test backend
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops.kernel_interpret() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        ops.kernel_interpret()


def test_fallback_counters_reset_and_scope():
    """`reset_fallback_counters` zeroes all three counters and re-arms the
    warn-once flags; `scoped_fallback_counters` reports only its block's
    deltas while the globals keep accumulating for backend snapshots."""
    ops.reset_fallback_counters()
    assert ops.fallback_counters() == {"prefill": 0, "paged": 0,
                                       "finalize": 0}
    shape = dict(nc=16, window=W, m=4, k_width=K, g=2, d=16)
    with pytest.warns(RuntimeWarning, match="VMEM budget"):
        with ops.scoped_fallback_counters() as fb:
            assert not ops.use_prefill_kernel("kernel", **shape, budget=64)
    assert fb == {"prefill": 1, "paged": 0, "finalize": 0}
    assert ops.fallback_counters()["prefill"] == 1   # global still counts
    with ops.scoped_fallback_counters() as fb2:
        pass
    assert fb2 == {"prefill": 0, "paged": 0, "finalize": 0}
    ops.reset_fallback_counters()
    # the warn flag is re-armed: the next budget fallback warns again
    with pytest.warns(RuntimeWarning, match="VMEM budget"):
        ops.use_prefill_kernel("kernel", **shape, budget=64)
    ops.reset_fallback_counters()


def test_paged_kernel_dma_pipeline_parity(monkeypatch):
    """REPRO_DMA_PIPELINE=0 (serial expert-row DMAs) and =1 (whole tiles
    in flight, the next one gathered while one is computed) produce
    identical decode steps — the pipeline only reorders copies into
    disjoint destination rows."""
    cfg_x, cfg_k = _paged_pair(s_route=2)
    runs = {}
    for mode in ("0", "1"):
        monkeypatch.setenv("REPRO_DMA_PIPELINE", mode)
        runs[mode] = []
        _, st_k = _drive(cfg_x, cfg_k, offs=[0, 3, 7], n_steps=12,
                         record=runs[mode])
        runs[mode].append(np.asarray(st_k.k_pool))
    for a, b in zip(runs["0"], runs["1"], strict=True):
        np.testing.assert_array_equal(a, b)


def test_block_q_env_default(monkeypatch):
    """REPRO_BLOCK_Q feeds `ops.default_block_q`, reachable via
    AttnConfig.block_q = 0.  Checked on the pallas routed path, which is
    block-size INVARIANT (the span path's documented drop condition
    depends on block size, so it is not a valid invariance probe)."""
    q, k, v = _qkv(n=128)
    cfg = MiTAConfig(m=8, k=16, s=1, causal=True)
    ref = mita_attention_sparse(q, k, v, cfg, impl="pallas", block_q=128)
    monkeypatch.setenv("REPRO_BLOCK_Q", "32")
    assert ops.default_block_q() == 32
    out = mita_attention_sparse(q, k, v, cfg, impl="pallas",
                                block_q=ops.default_block_q())
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)
    # AttnConfig plumbs 0 -> env default (modules.attention_apply)
    from repro.models import modules as nn
    acfg = nn.AttnConfig(window=16, k=16, block_q=0)
    assert (acfg.block_q or ops.default_block_q()) == 32
