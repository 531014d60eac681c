"""Incremental (decode-time) MiTA — our LM-serving adaptation.

The paper (§D) defers LLM decoding to future work; this module supplies it.
The key observation: the landmark/expert structures of causal MiTA depend
only on *completed* windows, so they can be maintained incrementally next to
the KV cache:

  * every step appends (k, v) to the cache and accumulates the query into a
    running window sum;
  * every ``window`` steps the just-completed window is *finalized*: its
    landmark query (mean of the window's queries), landmark value
    (cross-attention over the whole past), and top-k expert indices are
    computed once — O(t·d) work amortized to O(t·d/window) per token;
  * each decoded token then attends to: the shared expert (all finalized
    landmark pairs, ≤ m_max), its top-s routed experts (s·k gathered cache
    rows), and the local causal window — O(m_max + s·k + window) per token,
    which is what makes 500k-token decode lowerable.

State is per layer; models stack states over layers (scan axis 0).
Landmarks are shared per KV-head group (DESIGN.md GQA adaptation).
"""

from __future__ import annotations

import dataclasses
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.combine import (NEG_INF, Partial, combine,
                                partial_from_logits, partial_from_scores)


class MiTADecodeState(NamedTuple):
    """Decode-time cache for one attention layer.

    Shapes (B batch, Hkv KV heads, C cache capacity, d head dim,
    M = C // window landmark capacity, K expert width):
      k_cache, v_cache: [B, Hkv, C, d]
      lm_q, lm_v:       [B, Hkv, M, d]   finalized landmark queries/values
      expert_idx:       [B, Hkv, M, K]   gathered top-k cache rows per expert
      expert_valid:     [B, Hkv, M, K]
      q_sum:            [B, Hkv, d]      running query sum, current window
      t:                []               tokens currently in the cache
    """

    k_cache: jax.Array
    v_cache: jax.Array
    lm_q: jax.Array
    lm_v: jax.Array
    expert_idx: jax.Array
    expert_valid: jax.Array
    q_sum: jax.Array
    t: jax.Array


@dataclasses.dataclass(frozen=True)
class DecodeConfig:
    window: int          # w — landmark window size (train-time N/m)
    k: int               # expert width
    s: int = 1           # routed experts per query
    capacity: int = 0    # C — cache capacity (set by init)
    # Externalize the every-w-steps landmark finalize into its own jitted
    # step (`mita_finalize_if_due`), called by the serving loop at window
    # boundaries.  The per-token decode step then carries no O(context)
    # branch (§Perf: the lax.cond finalize dominated the decode cell's
    # collective/memory terms even though it runs 1/w of steps).  Semantics
    # vs inline: the last token of each window routes among j instead of
    # j+1 experts (1/w of tokens, one-expert-stale routing).
    external_finalize: bool = False
    # Paged decode-step backend: "auto" (fused Pallas kernel on TPU when
    # its working set fits the VMEM budget; XLA gather path elsewhere),
    # "kernel" (force the kernel — interpret mode off-TPU — still bounded
    # by the budget), or "xla" (force the oracle).
    paged_impl: str = "auto"
    # Batched chunk-prefill backend, same tri-state (dispatched by
    # `kernels.ops.use_prefill_kernel`; REPRO_PREFILL_IMPL overrides).
    prefill_impl: str = "auto"
    # Paged landmark-finalize backend, same tri-state (dispatched by
    # `kernels.ops.use_finalize_kernel`; REPRO_FINALIZE_IMPL overrides).
    finalize_impl: str = "auto"
    # VMEM working-set budget for kernel dispatch; 0 = use the env/default
    # budget (`kernels.ops.vmem_budget_bytes`).
    vmem_budget: int = 0


def window_aligned(n: int, window: int) -> int:
    """Round a token count up to a whole number of landmark windows — the
    alignment every cache capacity and page boundary in this module (and
    the serving engine on top of it) must share."""
    return ((n + window - 1) // window) * window


def init_decode_state(batch: int, n_kv: int, head_dim: int, capacity: int,
                      cfg: DecodeConfig, dtype=jnp.bfloat16) -> MiTADecodeState:
    m_max = capacity // cfg.window
    z = lambda *s: jnp.zeros((batch, n_kv) + s, dtype)
    return MiTADecodeState(
        k_cache=z(capacity, head_dim), v_cache=z(capacity, head_dim),
        lm_q=z(m_max, head_dim), lm_v=z(m_max, head_dim),
        expert_idx=jnp.zeros((batch, n_kv, m_max, cfg.k), jnp.int32),
        expert_valid=jnp.zeros((batch, n_kv, m_max, cfg.k), bool),
        q_sum=jnp.zeros((batch, n_kv, head_dim), jnp.float32),
        t=jnp.zeros((), jnp.int32),
    )


def mita_prefill_state(q: jax.Array, k: jax.Array, v: jax.Array,
                       cfg: DecodeConfig, capacity: int) -> MiTADecodeState:
    """Build a decode state from a full-sequence prefill.

    q: [B, Hkv, G, N, d]; k, v: [B, Hkv, 1, N, d].  Landmark/expert caches
    are computed with the training-path functions so decode continues
    *exactly* where training-time causal MiTA leaves off.
    """
    from repro.core import mita as mref

    b, hkv, _, n, d = q.shape
    w = cfg.window
    m_cnt = n // w
    m_max = capacity // w
    dtype = k.dtype

    ql = jnp.mean(q, axis=2)                       # [B, Hkv, N, d] group-pool
    state = init_decode_state(b, hkv, d, capacity, cfg, dtype=dtype)

    if m_cnt > 0:
        mcfg = mref.MiTAConfig(m=m_cnt, k=cfg.k, s=cfg.s, causal=True)
        q_lm = jnp.mean(
            ql[:, :, : m_cnt * w].reshape(b, hkv, m_cnt, w, d), axis=3)
        s_kv = mref.landmark_scores(k[:, :, 0, :n], q_lm, mcfg)
        idx, valid = mref.topk_indices(s_kv, mcfg)
        v_lm = mref.landmark_values(v[:, :, 0, :n], s_kv)
        pad_m = m_max - m_cnt
        state = state._replace(
            lm_q=jnp.pad(q_lm.astype(dtype), ((0, 0), (0, 0), (0, pad_m), (0, 0))),
            lm_v=jnp.pad(v_lm.astype(dtype), ((0, 0), (0, 0), (0, pad_m), (0, 0))),
            expert_idx=jnp.pad(idx, ((0, 0), (0, 0), (0, pad_m), (0, 0))),
            expert_valid=jnp.pad(valid, ((0, 0), (0, 0), (0, pad_m), (0, 0))),
        )
    tail = ql[:, :, m_cnt * w:]                    # partial-window queries
    return state._replace(
        k_cache=jnp.pad(k[:, :, 0], ((0, 0), (0, 0), (0, capacity - n), (0, 0))),
        v_cache=jnp.pad(v[:, :, 0], ((0, 0), (0, 0), (0, capacity - n), (0, 0))),
        q_sum=jnp.sum(tail, axis=2).astype(jnp.float32),
        t=jnp.asarray(n, jnp.int32),
    )


# ------------------------------------------------- full-attention baseline --

class FullDecodeState(NamedTuple):
    k_cache: jax.Array   # [B, Hkv, C, d]
    v_cache: jax.Array
    t: jax.Array


def init_full_state(batch, n_kv, head_dim, capacity, dtype=jnp.bfloat16):
    z = lambda *s: jnp.zeros((batch, n_kv) + s, dtype)
    return FullDecodeState(k_cache=z(capacity, head_dim),
                           v_cache=z(capacity, head_dim),
                           t=jnp.zeros((), jnp.int32))


def full_prefill_state(k: jax.Array, v: jax.Array, capacity: int):
    """k, v: [B, Hkv, 1, N, d]."""
    n = k.shape[-2]
    pad = ((0, 0), (0, 0), (0, capacity - n), (0, 0))
    return FullDecodeState(k_cache=jnp.pad(k[:, :, 0], pad),
                           v_cache=jnp.pad(v[:, :, 0], pad),
                           t=jnp.asarray(n, jnp.int32))


def full_decode_step(state: FullDecodeState, q, k_new, v_new):
    """O(t) per token — the quadratic baseline MiTA replaces.
    q: [B, Hkv, G, d]; k_new/v_new: [B, Hkv, d]."""
    d = q.shape[-1]
    cap = state.k_cache.shape[-2]
    t = state.t
    kc = jax.lax.dynamic_update_slice_in_dim(
        state.k_cache, k_new[:, :, None, :].astype(state.k_cache.dtype), t, axis=2)
    vc = jax.lax.dynamic_update_slice_in_dim(
        state.v_cache, v_new[:, :, None, :].astype(state.v_cache.dtype), t, axis=2)
    logits = jnp.einsum("bhgd,bhnd->bhgn", q, kc) / math.sqrt(d)
    mask = jnp.arange(cap)[None, None, None, :] <= t
    out = combine([partial_from_scores(logits, vc, mask=mask)])
    return out, FullDecodeState(k_cache=kc, v_cache=vc, t=t + 1)


def mita_finalize_if_due(state: MiTADecodeState,
                         cfg: DecodeConfig) -> MiTADecodeState:
    """External-finalize step: call from the serving loop every ``window``
    tokens (or unconditionally — it no-ops off-boundary).  This is its own
    jitted program so the per-token decode step stays O(m + s·k + w)."""
    return jax.lax.cond(
        (state.t % cfg.window == 0) & (state.t > 0),
        lambda s: _finalize_window(s, cfg, s.t),
        lambda s: s,
        state)


def _finalize_window(state: MiTADecodeState, cfg: DecodeConfig,
                     t_new: jax.Array) -> MiTADecodeState:
    """Finalize landmark i = t_new//w - 1 from the accumulated query sum."""
    d = state.k_cache.shape[-1]
    cap = state.k_cache.shape[-2]
    i = t_new // cfg.window - 1
    q_lm = (state.q_sum / cfg.window).astype(state.k_cache.dtype)  # [B,Hkv,d]

    scores = jnp.einsum("bhnd,bhd->bhn", state.k_cache, q_lm) / math.sqrt(d)
    visible = jnp.arange(cap)[None, None, :] < t_new
    scores = jnp.where(visible, scores.astype(jnp.float32), NEG_INF)
    top_vals, top_idx = jax.lax.top_k(scores, cfg.k)        # [B,Hkv,K]
    valid = top_vals > NEG_INF / 2
    p = jax.nn.softmax(scores, axis=-1)
    v_lm = jnp.einsum("bhn,bhnd->bhd",
                      p.astype(state.v_cache.dtype), state.v_cache)

    return state._replace(
        lm_q=state.lm_q.at[:, :, i, :].set(q_lm),
        lm_v=state.lm_v.at[:, :, i, :].set(v_lm),
        expert_idx=state.expert_idx.at[:, :, i, :].set(top_idx),
        expert_valid=state.expert_valid.at[:, :, i, :].set(valid),
        q_sum=jnp.zeros_like(state.q_sum),
    )


def mita_decode_step(state: MiTADecodeState, q: jax.Array, k_new: jax.Array,
                     v_new: jax.Array, cfg: DecodeConfig) -> tuple[jax.Array, MiTADecodeState]:
    """One decode step.

    Args:
      q:     [B, Hkv, G, d] new queries (G = query heads per KV group).
      k_new: [B, Hkv, d] new key;  v_new: [B, Hkv, d] new value.
    Returns: (output [B, Hkv, G, d], updated state).
    """
    b, hkv, g, d = q.shape
    cap = state.k_cache.shape[-2]
    m_max = state.lm_q.shape[-2]
    t = state.t

    # 1. append to cache, accumulate window query sum
    state = state._replace(
        k_cache=jax.lax.dynamic_update_slice_in_dim(
            state.k_cache, k_new[:, :, None, :].astype(state.k_cache.dtype), t, axis=2),
        v_cache=jax.lax.dynamic_update_slice_in_dim(
            state.v_cache, v_new[:, :, None, :].astype(state.v_cache.dtype), t, axis=2),
        q_sum=state.q_sum + jnp.mean(q, axis=2).astype(jnp.float32),
    )
    t_new = t + 1

    # 2. finalize the window if it just completed (amortized O(t/w) per step)
    if not cfg.external_finalize:
        state = jax.lax.cond(
            t_new % cfg.window == 0,
            lambda s: _finalize_window(s, cfg, t_new),
            lambda s: s,
            state)

    # 3. attend: shared + routed + local window
    if cfg.external_finalize:
        # the serving loop finalizes at window boundaries; the last token of
        # a window does not yet see its own window's landmark
        m_cnt = t // cfg.window
    else:
        m_cnt = t_new // cfg.window  # finalized landmarks
    lm_mask = jnp.arange(m_max)[None, None, None, :] < m_cnt

    # routing / shared logits: [B, Hkv, G, M]
    r = jnp.einsum("bhgd,bhmd->bhgm", q, state.lm_q) / math.sqrt(d)
    r = jnp.where(lm_mask, r.astype(jnp.float32), NEG_INF)
    parts: list[Partial] = [partial_from_scores(r, state.lm_v)]

    # routed experts: gather s·k cache rows per (b, h, g)
    s_ = min(cfg.s, m_max)
    _, e_idx = jax.lax.top_k(r, s_)                         # [B,Hkv,G,s]
    e_ok = jnp.take_along_axis(r, e_idx, axis=-1) > NEG_INF / 2
    flat_e = e_idx.reshape(b, hkv, g * s_)
    rows = jnp.take_along_axis(
        state.expert_idx.reshape(b, hkv, m_max, cfg.k),
        flat_e[..., None], axis=2)                          # [B,Hkv,g*s,K]
    rows_valid = jnp.take_along_axis(
        state.expert_valid, flat_e[..., None], axis=2)
    rows = rows.reshape(b, hkv, g * s_ * cfg.k)
    k_sel = jnp.take_along_axis(state.k_cache, rows[..., None], axis=2)
    v_sel = jnp.take_along_axis(state.v_cache, rows[..., None], axis=2)
    k_sel = k_sel.reshape(b, hkv, g, s_ * cfg.k, d)
    v_sel = v_sel.reshape(b, hkv, g, s_ * cfg.k, d)
    logits = jnp.einsum("bhgd,bhgkd->bhgk", q, k_sel) / math.sqrt(d)
    mask = (rows_valid.reshape(b, hkv, g, s_, cfg.k)
            & e_ok[..., None]).reshape(b, hkv, g, s_ * cfg.k)
    parts.append(partial_from_logits(logits, v_sel, mask=mask))

    # local: the query's OWN window [ (t//w)*w, t ] — note t//w, not
    # t_new//w: the last token of a window still attends its window locally
    # (matching training-time `_local_partial`).
    start = (t // cfg.window) * cfg.window
    k_loc = jax.lax.dynamic_slice_in_dim(state.k_cache, start, cfg.window, axis=2)
    v_loc = jax.lax.dynamic_slice_in_dim(state.v_cache, start, cfg.window, axis=2)
    loc_logits = jnp.einsum("bhgd,bhwd->bhgw", q, k_loc) / math.sqrt(d)
    loc_mask = (jnp.arange(cfg.window)[None, None, None, :] + start) < t_new
    parts.append(partial_from_scores(loc_logits, v_loc, mask=loc_mask))

    out = combine(parts)
    return out, state._replace(t=t_new)


# ----------------------------------------------------------- paged decode --
#
# Serving-engine form of the same cache: instead of one monolithic
# [B, Hkv, C, d] cache per request batch, a single KV pool per layer is
# shared by every request.  A request owns window-aligned *pages* (page size
# == cfg.window, so one landmark per completed page); which rows a slot sees
# is entirely decided by its page table, and slots advance independently
# (per-slot t) — the continuous-batching engine (repro.serve) keeps the slot
# batch full regardless of per-request progress.
#
# Layout choices:
#   * pool rows lead ([R+1, Hkv, d]): append is a 1-row scatter at
#     rows_new[slot], gathers are plain row indexing.  Row R is a write
#     scratch for inactive slots so the step has no host-side branching.
#   * expert_idx stores GLOBAL pool rows (page_id * w + offset), assigned at
#     finalize/pack time — the decode-step gather needs no page-table lookup.


class PagedMiTAState(NamedTuple):
    """Paged decode cache for one layer, shared across S request slots.

    Shapes (R = n_pages * window pool rows, S slots,
    M = pages_per_slot = landmark capacity per slot, K expert width):
      k_pool, v_pool:   [R + 1, Hkv, L]  f32, L = `ops.pool_lanes(d)` lanes
                                         per head row (d, or d rounded up
                                         to 128-lane tiles on the TPU: the
                                         kernels DMA one head's row, which
                                         Mosaic allows only for whole tiles
                                         of 32-bit words; values written
                                         from a bf16 model stay bf16-exact);
                                         row R is a write scratch for
                                         inactive slots / padded tokens
      lm_q, lm_v:       [S, Hkv, M, d]   finalized landmark queries/values
      expert_idx:       [S, Hkv, M, K]   GLOBAL pool rows per expert
                                         (page_id * window + offset)
      expert_valid:     [S, Hkv, M, K]
      q_sum:            [S, Hkv, d]      running query sum, current window
                                         (f32; resumed across prefill chunks)
      pre_lm_q:         [S, Hkv, M, d]   transient PROMPT landmark queries —
                                         the training path pools the prompt's
                                         landmarks over n//m-sized windows
                                         (the `mita_prefill_state` quirk for
                                         non-window-aligned prompts), so the
                                         chunked prefill carries this second
                                         landmark-query set across chunks;
                                         dead weight after the last chunk
      pre_q_sum:        [S, Hkv, d]      running query sum of the open
                                         n//m-sized prompt window (f32)

    Ownership contract: per-slot progress (t), page tables, and activity
    live on the host and are passed into each step — the scheduler owns
    them and guarantees every page a step may WRITE (prefill rows at
    t >= the chunk's resume point, the decode append row at t) is
    referenced by exactly one slot.  Pages may be read-shared (the prefix
    cache attaches one page to many slots' tables, ref-counted), but a
    shared page is always a fully-committed prompt window that no program
    writes again: appends land past every slot's shared prefix, and the
    fused kernels' in-place aliasing only ever targets the writing slot's
    exclusively-owned page (docs/serving.md, invariant 1)."""

    k_pool: jax.Array
    v_pool: jax.Array
    lm_q: jax.Array
    lm_v: jax.Array
    expert_idx: jax.Array
    expert_valid: jax.Array
    q_sum: jax.Array
    pre_lm_q: jax.Array
    pre_q_sum: jax.Array


def init_paged_state(n_kv: int, head_dim: int, n_pages: int, n_slots: int,
                     pages_per_slot: int, cfg: DecodeConfig,
                     dtype=jnp.bfloat16) -> PagedMiTAState:
    from repro.kernels import ops

    rows = n_pages * cfg.window + 1
    lanes = ops.pool_lanes(head_dim)
    return PagedMiTAState(
        k_pool=jnp.zeros((rows, n_kv, lanes), jnp.float32),
        v_pool=jnp.zeros((rows, n_kv, lanes), jnp.float32),
        lm_q=jnp.zeros((n_slots, n_kv, pages_per_slot, head_dim), dtype),
        lm_v=jnp.zeros((n_slots, n_kv, pages_per_slot, head_dim), dtype),
        expert_idx=jnp.zeros((n_slots, n_kv, pages_per_slot, cfg.k),
                             jnp.int32),
        expert_valid=jnp.zeros((n_slots, n_kv, pages_per_slot, cfg.k), bool),
        q_sum=jnp.zeros((n_slots, n_kv, head_dim), jnp.float32),
        pre_lm_q=jnp.zeros((n_slots, n_kv, pages_per_slot, head_dim), dtype),
        pre_q_sum=jnp.zeros((n_slots, n_kv, head_dim), jnp.float32),
    )


def _paged_finalize(state: PagedMiTAState, page_table: jax.Array,
                    t_new: jax.Array, due: jax.Array,
                    cfg: DecodeConfig) -> PagedMiTAState:
    """Finalize landmark i = t_new//w - 1 for every slot with due[s].

    Computed for all slots, committed where ``due`` — identical per-slot
    semantics to `_finalize_window` on a monolithic cache whose rows are the
    slot's pages in table order.

    Backend dispatch (``cfg.finalize_impl``,
    `kernels.ops.use_finalize_kernel`): the fused per-(slot, KV-head)
    Pallas kernel (`kernels.mita_paged_finalize`) when it fits the VMEM
    budget; the XLA gather path below is the fallback and the bit-exact
    oracle.
    """
    from repro.kernels import ops
    from repro.kernels.ops import gather_pages

    w = cfg.window
    n_slots, _, m_max, _ = state.expert_idx.shape
    d = state.q_sum.shape[-1]
    ctx = m_max * w

    if ops.use_finalize_kernel(
            cfg.finalize_impl, window=w, m=m_max, k_width=cfg.k,
            d=state.k_pool.shape[-1],
            itemsize=state.k_pool.dtype.itemsize, budget=cfg.vmem_budget):
        lm_q, lm_v, ei, ev, q_sum = ops.paged_finalize(
            state.q_sum, state.lm_q, state.lm_v, state.expert_idx,
            state.expert_valid, state.k_pool, state.v_pool, page_table,
            t_new, due, window=w, k_width=cfg.k)
        return state._replace(lm_q=lm_q, lm_v=lm_v, expert_idx=ei,
                              expert_valid=ev.astype(bool), q_sum=q_sum)

    # gather only pages covering positions < t_new; unowned table entries
    # redirect to the scratch row (they are masked below either way)
    owned = (t_new + w - 1) // w
    k_ctx = gather_pages(state.k_pool, page_table, w, d, owned=owned)
    v_ctx = gather_pages(state.v_pool, page_table, w, d, owned=owned)
    q_lm = (state.q_sum / w).astype(state.lm_q.dtype)  # [S, Hkv, d]

    scores = jnp.einsum("schd,shd->shc", k_ctx, q_lm) / math.sqrt(d)
    visible = jnp.arange(ctx)[None, None, :] < t_new[:, None, None]
    scores = jnp.where(visible, scores.astype(jnp.float32), NEG_INF)
    top_vals, top_loc = jax.lax.top_k(scores, cfg.k)     # [S, Hkv, K] ctx idx
    valid = top_vals > NEG_INF / 2
    # ctx position -> global pool row via the page table
    ctx_rows = (page_table[:, :, None] * w
                + jnp.arange(w)[None, None, :]).reshape(n_slots, ctx)
    rows = jnp.take_along_axis(
        jnp.broadcast_to(ctx_rows[:, None, :], top_loc.shape[:-1] + (ctx,)),
        top_loc, axis=-1)
    p = jax.nn.softmax(scores, axis=-1)
    v_lm = jnp.einsum("shc,schd->shd", p.astype(state.v_pool.dtype), v_ctx
                      ).astype(state.lm_v.dtype)

    i = t_new // w - 1                                   # [S]
    sel = due[:, None] & (jnp.arange(m_max)[None, :] == i[:, None])  # [S, M]
    sel4 = sel[:, None, :, None]
    return state._replace(
        lm_q=jnp.where(sel4, q_lm[:, :, None, :], state.lm_q),
        lm_v=jnp.where(sel4, v_lm[:, :, None, :], state.lm_v),
        expert_idx=jnp.where(sel4, rows[:, :, None, :], state.expert_idx),
        expert_valid=jnp.where(sel4, valid[:, :, None, :], state.expert_valid),
        q_sum=jnp.where(due[:, None, None], 0.0, state.q_sum),
    )


def mita_paged_finalize(state: PagedMiTAState, page_table: jax.Array,
                        t: jax.Array, due: jax.Array,
                        cfg: DecodeConfig) -> PagedMiTAState:
    """External-finalize entry point for the serving loop (its own jitted
    program).  ``due`` comes from the scheduler: active slots whose last
    completed window has not been finalized yet (t % w == 0 and the window
    count exceeds the finalized count — the scheduler tracks the latter, so
    a freshly prefilled boundary-aligned slot is never re-finalized from a
    zero q_sum)."""
    return _paged_finalize(state, page_table, t, due, cfg)


def mita_paged_decode_step(state: PagedMiTAState, q: jax.Array,
                           k_new: jax.Array, v_new: jax.Array,
                           page_table: jax.Array, t: jax.Array,
                           active: jax.Array,
                           cfg: DecodeConfig) -> tuple[jax.Array, PagedMiTAState]:
    """One fused decode step for the whole slot batch.

    Args:
      q:          [S, Hkv, G, d] new queries.
      k_new:      [S, Hkv, d]; v_new: [S, Hkv, d].
      page_table: [S, M] int32 page ids owned by each slot (unused entries
                  must hold any in-bounds page id; they are masked).
      t:          [S] int32 tokens already in each slot's cache.
      active:     [S] bool — inactive slots write to the scratch row and
                  return zeros.
    Returns: (output [S, Hkv, G, d], updated state).  The caller advances
    ``t`` for active slots.

    This is ONE program for the whole batch regardless of per-request
    progress: positions, page tables, and activity are data, not shape.
    Scheduler invariants relied on (docs/serving.md): the page named by
    ``page_table[s, t[s] // w]`` exists for every active slot (the engine
    allocates the next page BEFORE the step that appends into it), and
    pages of distinct slots are disjoint, so the per-slot 1-row scatter
    can never race another slot's rows.

    Backend dispatch (``cfg.paged_impl``, `kernels.ops.use_paged_kernel`):
    the fused Pallas kernel (`kernels.mita_paged_attn`) replaces the
    append + gather-then-attend below when it fits the VMEM budget; the
    XLA path here stays as the fallback and the parity oracle.  Inline
    finalize needs the appended row in the pool before scoring, so in
    that mode the append/finalize run in XLA and the kernel only attends."""
    from repro.kernels import ops

    n_slots, hkv, g, d = q.shape
    w = cfg.window
    m_max = state.lm_q.shape[-2]
    scratch = state.k_pool.shape[0] - 1
    s_ = min(cfg.s, m_max)

    use_kernel = ops.use_paged_kernel(
        cfg.paged_impl, window=w, m=m_max, k_width=cfg.k, g=g,
        d=state.k_pool.shape[-1], itemsize=state.k_pool.dtype.itemsize,
        budget=cfg.vmem_budget)

    # 1. append to the slot's current page, accumulate window query sum
    # (the kernel fuses the append when it also owns the attend)
    cur_page = jnp.take_along_axis(page_table, (t // w)[:, None], axis=1)[:, 0]
    rows_new = jnp.where(active, cur_page * w + t % w, scratch)
    state = state._replace(
        q_sum=state.q_sum + jnp.where(
            active[:, None, None], jnp.mean(q, axis=2).astype(jnp.float32), 0.0),
    )
    t_new = t + 1
    fuse_append = use_kernel and cfg.external_finalize
    if not fuse_append:
        state = state._replace(
            k_pool=ops.scatter_pool_rows(state.k_pool, rows_new, k_new),
            v_pool=ops.scatter_pool_rows(state.v_pool, rows_new, v_new),
        )

    # 2. finalize slots whose window just completed (masked, all-slot
    # compute).  External mode defers this to `mita_paged_finalize`, called
    # by the scheduler only on steps where some slot is actually due — the
    # hot step then stays O(m + s·k + w) per token.
    if not cfg.external_finalize:
        due = active & (t_new % w == 0)
        state = _paged_finalize(state, page_table, t_new, due, cfg)
        m_cnt = t_new // w
    else:
        m_cnt = t // w

    if use_kernel:
        out, kp, vp = ops.paged_decode_attend(
            q, k_new, v_new, state.lm_q, state.lm_v, state.expert_idx,
            state.expert_valid, state.k_pool, state.v_pool, page_table, t,
            active, m_cnt, window=w, n_route=s_, fuse_append=fuse_append)
        return out.astype(q.dtype), state._replace(k_pool=kp, v_pool=vp)

    # 3. attend: shared + routed + local window (same branch math as
    # `mita_decode_step`, with every cache access routed through the pool)
    gather_pages = ops.gather_pages
    gather_pool_rows = ops.gather_pool_rows
    lm_mask = jnp.arange(m_max)[None, None, None, :] < m_cnt[:, None, None, None]
    r = jnp.einsum("shgd,shmd->shgm", q, state.lm_q) / math.sqrt(d)
    r = jnp.where(lm_mask, r.astype(jnp.float32), NEG_INF)
    parts: list[Partial] = [partial_from_scores(r, state.lm_v)]

    _, e_idx = jax.lax.top_k(r, s_)                     # [S, Hkv, G, s]
    e_ok = jnp.take_along_axis(r, e_idx, axis=-1) > NEG_INF / 2
    flat_e = e_idx.reshape(n_slots, hkv, g * s_)
    rows = jnp.take_along_axis(state.expert_idx, flat_e[..., None], axis=2)
    rows_valid = jnp.take_along_axis(state.expert_valid, flat_e[..., None],
                                     axis=2)
    rows = rows.reshape(n_slots, hkv, g * s_ * cfg.k)
    k_sel = gather_pool_rows(state.k_pool, rows, d).reshape(
        n_slots, hkv, g, s_ * cfg.k, d)
    v_sel = gather_pool_rows(state.v_pool, rows, d).reshape(
        n_slots, hkv, g, s_ * cfg.k, d)
    logits = jnp.einsum("shgd,shgkd->shgk", q, k_sel) / math.sqrt(d)
    mask = (rows_valid.reshape(n_slots, hkv, g, s_, cfg.k)
            & e_ok[..., None]).reshape(n_slots, hkv, g, s_ * cfg.k)
    parts.append(partial_from_logits(logits, v_sel, mask=mask))

    # local: the slot's own (current) page
    k_loc = jnp.swapaxes(gather_pages(state.k_pool, cur_page[:, None], w, d),
                         1, 2)                            # [S, Hkv, w, d]
    v_loc = jnp.swapaxes(gather_pages(state.v_pool, cur_page[:, None], w, d),
                         1, 2)
    loc_logits = jnp.einsum("shgd,shwd->shgw", q, k_loc) / math.sqrt(d)
    start = (t // w) * w
    loc_mask = (jnp.arange(w)[None, :] + start[:, None]
                < t_new[:, None])[:, None, None, :]
    parts.append(partial_from_scores(loc_logits, v_loc, mask=loc_mask))

    out = combine(parts).astype(q.dtype)
    return jnp.where(active[:, None, None, None], out, 0.0), state


def mita_paged_landmark_attend(state: PagedMiTAState, q: jax.Array,
                               m_cnt: jax.Array,
                               cfg: DecodeConfig) -> jax.Array:
    """Compressed-branch-only attention for the speculative drafter.

    The shared landmark branch alone — no expert gather, no page walk, no
    KV append, no q_sum accumulation, no state mutation of any kind.  This
    is the cheap standalone approximation MiTA's compress-and-route design
    gives away for free: a draft token costs O(m) reads of slot-resident
    landmark tiles instead of O(m + s·k + w) with two pool gathers.

    Args:
      q:      [S, Hkv, G, d] draft-position queries (RoPE'd by the caller).
      m_cnt:  [S] finalized landmark count per slot (the drafter sees the
              landmarks committed so far; any in-flight window stays
              invisible, exactly like the external-finalize decode rule).
    Returns [S, Hkv, G, d].  Slots with m_cnt == 0 attend a zero-value
    sink instead (deterministic output, no NaNs) — their drafts are
    near-random and simply get rejected at verify time.
    """
    d = q.shape[-1]
    m_max = state.lm_q.shape[-2]
    lm_mask = (jnp.arange(m_max)[None, None, None, :]
               < m_cnt[:, None, None, None])
    r = jnp.einsum("shgd,shmd->shgm", q, state.lm_q) / math.sqrt(d)
    r = jnp.where(lm_mask, r.astype(jnp.float32), NEG_INF)
    sink = partial_from_scores(
        jnp.zeros(r.shape[:-1] + (1,), jnp.float32),
        jnp.zeros_like(state.lm_v[:, :, :1]),
        mask=(m_cnt == 0)[:, None, None, None])
    return combine([partial_from_scores(r, state.lm_v), sink])


def pack_prefill_into_pages(state: PagedMiTAState, pre: MiTADecodeState,
                            slot: jax.Array, pages: jax.Array,
                            cfg: DecodeConfig) -> PagedMiTAState:
    """Copy a single-request monolithic prefill state into a slot's pages.

    Shape contract: ``pre`` has B == 1 and a window-aligned cache capacity
    C = P_used * w; ``pages`` is ``[P_used]`` int32 page ids in table order
    (token order).  KV rows land at ``pages[c // w] * w + c % w`` and expert
    indices are rebased from cache-local rows to GLOBAL pool rows, so the
    decode-step gather needs no page-table lookup afterwards.

    Scheduler invariant preserved: only ``slot``'s landmark/expert/q_sum
    entries and the rows of ``pages`` are written — a pack can never touch
    pages owned by another slot (invariant 1 in docs/serving.md).  The open
    final window's ``q_sum`` is carried into the slot, so decode (or a later
    `mita_chunk_prefill` call) resumes the window exactly where the prefill
    left it."""
    from repro.kernels import ops

    w = cfg.window
    c_pre = pre.k_cache.shape[-2]
    if c_pre % w:
        raise ValueError(f"prefill capacity {c_pre} not window-aligned")
    p_used = c_pre // w
    m_max = state.lm_q.shape[-2]
    m_pre = pre.lm_q.shape[-2]
    if p_used > m_max or m_pre > m_max:
        raise ValueError("request needs more pages than a slot owns")

    dst_rows = (pages[:, None] * w + jnp.arange(w)).reshape(-1)   # [C]
    k_rows = jnp.swapaxes(pre.k_cache[0], 0, 1)                   # [C, Hkv, d]
    v_rows = jnp.swapaxes(pre.v_cache[0], 0, 1)

    # cache-local expert rows -> global pool rows
    loc = pre.expert_idx[0]                                       # [Hkv, M', K]
    grows = pages[loc // w] * w + loc % w

    pad_m = ((0, 0), (0, m_max - m_pre), (0, 0))
    return state._replace(
        k_pool=ops.scatter_pool_rows(state.k_pool, dst_rows, k_rows),
        v_pool=ops.scatter_pool_rows(state.v_pool, dst_rows, v_rows),
        lm_q=state.lm_q.at[slot].set(
            jnp.pad(pre.lm_q[0], pad_m).astype(state.lm_q.dtype)),
        lm_v=state.lm_v.at[slot].set(
            jnp.pad(pre.lm_v[0], pad_m).astype(state.lm_v.dtype)),
        expert_idx=state.expert_idx.at[slot].set(jnp.pad(grows, pad_m)),
        expert_valid=state.expert_valid.at[slot].set(
            jnp.pad(pre.expert_valid[0], pad_m)),
        q_sum=state.q_sum.at[slot].set(pre.q_sum[0]),
    )


# --------------------------------------------------------- chunked prefill --
#
# Serving engines bound admission latency by splitting a long prompt into
# fixed-size chunks and interleaving chunk prefill with the decode batch
# (vLLM-style chunked prefill).  `mita_chunk_prefill` is the MiTA form of
# one such chunk: it appends the chunk's KV rows to the slot's pages,
# finalizes every landmark window the chunk completes (scores over the
# WHOLE gathered past, exactly like `_finalize_window`), resumes the open
# window's query sum across chunk boundaries, and computes the chunk's
# attention outputs so the model forward over the chunk is exact.
#
# The same op is the recompute path for preemption: a preempted request is
# rebuilt by chunk-prefilling prompt + generated tokens.  Because decode ran
# with a given finalize mode, positions >= n_train replicate the DECODE
# availability rule (external mode: the last token of a window routes one
# expert stale) while positions < n_train replicate the training/prefill
# rule — so the rebuilt state continues bit-compatibly with the state the
# request had when it was evicted.


def mita_chunk_prefill(state: PagedMiTAState, q: jax.Array, k: jax.Array,
                       v: jax.Array, page_table: jax.Array, slot: jax.Array,
                       t0: jax.Array, n_valid: jax.Array, n_train: jax.Array,
                       cfg: DecodeConfig) -> tuple[jax.Array, PagedMiTAState]:
    """Prefill one chunk of a single slot's prompt into the paged pool.

    Args:
      q:          [Hkv, G, nc, d] chunk queries (RoPE'd at positions
                  ``t0 + arange(nc)``).
      k, v:       [Hkv, nc, d] chunk keys/values.
      page_table: [M] int32 — the slot's page-table row.  Pages covering
                  positions < t0 + n_valid must already be allocated.
      slot:       scalar int32 — which slot's landmark/expert/q_sum to edit.
      t0:         scalar int32 — tokens already packed for this slot (the
                  chunk covers positions [t0, t0 + n_valid)).  Need NOT be
                  window-aligned: an open window is resumed from the slot's
                  ``q_sum``.
      n_valid:    scalar int32 — valid tokens in the chunk; positions >=
                  n_valid are padding (their KV rows go to the scratch row,
                  their outputs are garbage and must be ignored).
      n_train:    scalar int32 — training/decode semantics boundary.  For a
                  fresh prompt pass t0 + n_valid (everything is "prompt");
                  for preemption recompute pass the ORIGINAL prompt length
                  so recomputed generated positions see landmarks exactly as
                  the decode step did (external-finalize staleness included).

    Returns (out [Hkv, G, nc, d], updated state).  One compiled program per
    chunk shape serves every chunk of every request — chunk index, length
    and resume point are data.

    Scheduler invariants preserved: writes touch only ``slot``'s state rows,
    the rows of pages named by ``page_table``, and the scratch row; landmark
    i of the slot summarizes exactly the tokens of ``page_table[i]``.
    """
    from repro.kernels.ops import (gather_pages, gather_pool_rows,
                                   scatter_pool_rows)

    w = cfg.window
    hkv, g, nc, d = q.shape
    m_slot = page_table.shape[0]
    ctx = m_slot * w
    scratch = state.k_pool.shape[0] - 1

    pos = t0 + jnp.arange(nc)                       # [nc] global positions
    valid_tok = jnp.arange(nc) < n_valid            # [nc]

    # 1. append chunk KV to the slot's pages (padding -> scratch row)
    page_idx = jnp.clip(pos // w, 0, m_slot - 1)
    dst = jnp.where(valid_tok, page_table[page_idx] * w + pos % w, scratch)
    kp = scatter_pool_rows(state.k_pool, dst, jnp.swapaxes(k, 0, 1))
    vp = scatter_pool_rows(state.v_pool, dst, jnp.swapaxes(v, 0, 1))

    # gathered slot context in token order: [ctx, Hkv, d] — only pages
    # covering positions < t0 + n_valid are real; later table entries
    # redirect to the scratch row (all reads past the valid prefix are
    # masked below, so this only avoids gathering unowned pages)
    owned = ((t0 + n_valid + w - 1) // w)[None]
    k_ctx = gather_pages(kp, page_table[None], w, d, owned=owned)[0]
    v_ctx = gather_pages(vp, page_table[None], w, d, owned=owned)[0]

    # 2. finalize every window the chunk completes (windows [m0, m_new)),
    # resuming the open window's query sum from the previous chunk
    m0 = t0 // w
    m_new = (t0 + n_valid) // w
    li = jnp.arange(m_slot)                         # landmark slot ids [M]
    ql = jnp.mean(q, axis=1)                        # [Hkv, nc, d] group pool
    win_of = pos // w
    tok_in_win = valid_tok[None, :] & (win_of[None, :] == li[:, None])
    sums = jnp.einsum("mn,hnd->hmd", tok_in_win.astype(jnp.float32),
                      ql.astype(jnp.float32))       # [Hkv, M, d]
    resume = (li == m0)[None, :, None] & (t0 % w != 0)
    sums = sums + jnp.where(resume, state.q_sum[slot][:, None, :], 0.0)

    q_lm_new = (sums / w).astype(state.lm_q.dtype)  # [Hkv, M, d]
    ends = (li + 1) * w                             # [M] strict window ends
    s_lm = jnp.einsum("chd,hmd->hmc", k_ctx, q_lm_new) / math.sqrt(d)
    vis = jnp.arange(ctx)[None, None, :] < ends[None, :, None]
    s_lm = jnp.where(vis, s_lm.astype(jnp.float32), NEG_INF)
    top_vals, top_loc = jax.lax.top_k(s_lm, cfg.k)  # [Hkv, M, K] ctx idx
    new_valid = top_vals > NEG_INF / 2
    ctx_rows = (page_table[:, None] * w + jnp.arange(w)[None, :]).reshape(ctx)
    new_rows = ctx_rows[top_loc]                    # ctx idx -> global rows
    p_lm = jax.nn.softmax(s_lm, axis=-1)
    v_lm_new = jnp.einsum("hmc,chd->hmd", p_lm.astype(vp.dtype), v_ctx
                          ).astype(state.lm_v.dtype)

    commit = ((li >= m0) & (li < m_new))[None, :, None]
    lm_q_s = jnp.where(commit, q_lm_new, state.lm_q[slot])
    lm_v_s = jnp.where(commit, v_lm_new, state.lm_v[slot])
    ei_s = jnp.where(commit, new_rows, state.expert_idx[slot])
    ev_s = jnp.where(commit, new_valid, state.expert_valid[slot])
    # open window after the chunk: tail of this chunk, plus the resumed sum
    # if the chunk closed no window at all
    tail = jnp.einsum("n,hnd->hd",
                      (valid_tok & (win_of == m_new)).astype(jnp.float32),
                      ql.astype(jnp.float32))
    q_sum_s = tail + jnp.where((m_new == m0) & (t0 % w != 0),
                               state.q_sum[slot], 0.0)

    # 3. chunk attention: shared + routed + local, same branch math as the
    # training path / decode step, with per-position landmark availability
    is_train = (pos < n_train)[:, None]             # [nc, 1]
    avail_train = ends[None, :] <= pos[:, None] + 1
    avail_dec = ends[None, :] <= pos[:, None] if cfg.external_finalize \
        else avail_train
    avail = jnp.where(is_train, avail_train, avail_dec)   # [nc, M]

    r = jnp.einsum("hgnd,hmd->hgnm", q, lm_q_s) / math.sqrt(d)
    r = jnp.where(avail[None, None], r.astype(jnp.float32), NEG_INF)
    parts: list[Partial] = [partial_from_scores(r, lm_v_s[:, None])]

    s_ = min(cfg.s, m_slot)
    _, e_idx = jax.lax.top_k(r, s_)                 # [Hkv, G, nc, s]
    e_ok = jnp.take_along_axis(r, e_idx, axis=-1) > NEG_INF / 2
    flat_e = e_idx.reshape(hkv, g * nc * s_)
    rows = jnp.take_along_axis(ei_s, flat_e[..., None], axis=1)
    rows_valid = jnp.take_along_axis(ev_s, flat_e[..., None], axis=1)
    rows = rows.reshape(hkv, g * nc * s_ * cfg.k)
    k_sel = gather_pool_rows(kp, rows[None], d)[0].reshape(
        hkv, g, nc, s_ * cfg.k, d)
    v_sel = gather_pool_rows(vp, rows[None], d)[0].reshape(
        hkv, g, nc, s_ * cfg.k, d)
    logits = jnp.einsum("hgnd,hgnkd->hgnk", q, k_sel) / math.sqrt(d)
    mask = (rows_valid.reshape(hkv, g, nc, s_, cfg.k)
            & e_ok[..., None]).reshape(hkv, g, nc, s_ * cfg.k)
    parts.append(partial_from_logits(logits, v_sel, mask=mask))

    # local: each chunk position attends its own window, which may start in
    # a previous chunk (resume) — the gathered context covers both
    loc_idx = (jnp.clip(pos // w, 0, m_slot - 1) * w)[:, None] \
        + jnp.arange(w)[None, :]                    # [nc, w] ctx positions
    k_loc = jnp.moveaxis(k_ctx[loc_idx], 2, 0)      # [Hkv, nc, w, d]
    v_loc = jnp.moveaxis(v_ctx[loc_idx], 2, 0)
    loc_logits = jnp.einsum("hgnd,hnwd->hgnw", q, k_loc) / math.sqrt(d)
    loc_mask = (loc_idx <= pos[:, None])[None, None]
    parts.append(partial_from_logits(loc_logits, v_loc[:, None],
                                     mask=loc_mask))

    out = combine(parts).astype(q.dtype)
    return out, state._replace(
        k_pool=kp, v_pool=vp,
        lm_q=state.lm_q.at[slot].set(lm_q_s),
        lm_v=state.lm_v.at[slot].set(lm_v_s),
        expert_idx=state.expert_idx.at[slot].set(ei_s),
        expert_valid=state.expert_valid.at[slot].set(ev_s),
        q_sum=state.q_sum.at[slot].set(q_sum_s),
    )


# ------------------------------------------------- batched chunked prefill --
#
# `mita_batched_chunk_prefill` advances ONE window-aligned chunk for EVERY
# currently-prefilling slot in a single program — the serving engine's
# prefill work per step is then one dispatch of one compiled shape no matter
# how many requests are mid-prefill.  Which slots advance, their resume
# points, chunk validity, and the training/decode semantics boundary are all
# data ([S] vectors); inactive rows write only to the scratch row and pass
# their slot state through untouched.
#
# Unlike the single-slot op above, this one also serves NON-window-aligned
# prompts, replicating the monolithic head exactly so the engine needs no
# monolithic fallback.  The monolithic path has a quirk worth naming: for a
# prompt of n tokens the *training-path forward* (`attention_apply`) pools
# m = n // w landmark queries over windows of w' = n // m tokens and masks
# landmark visibility at (i+1) * w' — while `mita_prefill_state` builds the
# DECODE cache's landmarks from exact w-token query windows scored against
# the same (i+1) * w' key ends.  Both systems are therefore maintained per
# chunk:
#
#   * the "A" system (prompt positions < n_train): w'-pooled landmark
#     queries carried in `pre_lm_q` / `pre_q_sum`; landmark values and
#     expert tiles are recomputed each chunk from the gathered context
#     (append-only pages make the recompute exact), feeding the chunk's
#     attention outputs so the forward over the prompt equals the training
#     path, chunk boundaries notwithstanding;
#   * the "B" system (the decode cache): w-pooled landmark queries committed
#     into `lm_q` as soon as their query window completes, scores/values/
#     expert rows committed once the (i+1) * w' key context exists — for
#     window-aligned prompts w' == w and both systems coincide with the
#     single-slot op above.
#
# Generated positions (>= n_train, the preemption-recompute shape) attend
# through the B system with decode-time landmark availability, exactly like
# the single-slot op.  Backend dispatch (`cfg.prefill_impl`,
# `kernels.ops.use_prefill_kernel`): the fused Pallas kernel
# (`kernels.mita_chunk_prefill`) replaces this XLA path when its working set
# fits the VMEM budget; the XLA path stays as fallback and bit-exact oracle.


def _quirk_windows(n_train: jax.Array, w: int):
    """Per-slot prompt landmark structure: (m_train, m_a, w_a) where
    ``m_train`` counts the decode cache's w-sized prompt windows, and the
    training forward pools ``m_a = max(1, m_train)`` landmarks over
    ``w_a = n_train // m_a``-sized windows (the n//m quirk; w_a == w for
    window-aligned prompts).  All int32, safe for n_train == 0 rows."""
    m_train = n_train // w
    m_a = jnp.maximum(m_train, 1)
    w_a = jnp.maximum(n_train // m_a, 1)
    return m_train, m_a, w_a


def mita_batched_chunk_prefill(state: PagedMiTAState, q: jax.Array,
                               k: jax.Array, v: jax.Array,
                               page_table: jax.Array, slots: jax.Array,
                               t0: jax.Array, n_valid: jax.Array,
                               n_train: jax.Array, active: jax.Array,
                               cfg: DecodeConfig
                               ) -> tuple[jax.Array, PagedMiTAState]:
    """Prefill one chunk for every active row in one fused program.

    Rows are *jobs*, not slots: the engine packs the currently-prefilling
    slots (padded with DISTINCT idle slots to a fixed width P) so compute
    scales with the number of prefilling requests, not the slot-batch
    width.  All per-row quantities are data; P is the only shape.

    Args:
      q:          [P, Hkv, G, nc, d] chunk queries per row (RoPE'd at
                  positions ``t0[p] + arange(nc)``; garbage for inactive
                  rows).
      k, v:       [P, Hkv, nc, d] chunk keys/values.
      page_table: [P, M] int32 — each row's slot's page-table row.  Pages
                  covering positions < t0 + n_valid must be allocated.
      slots:      [P] int32 UNIQUE slot ids (duplicates would make the
                  state write-back order undefined).
      t0:         [P] int32 resume points (tokens already packed; always a
                  multiple of the chunk length, hence window-aligned).
      n_valid:    [P] int32 valid tokens per row; padding past it lands in
                  the scratch row and yields garbage outputs.
      n_train:    [P] int32 training/decode semantics boundary (original
                  prompt length) — positions >= n_train replicate decode-
                  time landmark availability, exactly as the single-slot op.
      active:     [P] bool — inactive rows leave every piece of their
                  slot's state (and every owned page) bit-identical.

    Returns (out [P, Hkv, G, nc, d], updated state).
    """
    from repro.kernels import ops

    w = cfg.window
    _, _, g, nc, d = q.shape
    m_slot = page_table.shape[1]
    s_ = min(cfg.s, m_slot)
    pdt = state.k_pool.dtype

    # gather the rows' slot state once; both backends compute compact
    # [P, ...] updates that are scattered back below
    lm_q_r = state.lm_q[slots]
    lm_v_r = state.lm_v[slots]
    ei_r = state.expert_idx[slots]
    ev_r = state.expert_valid[slots]
    qs_r = state.q_sum[slots]
    plm_r = state.pre_lm_q[slots]
    pqs_r = state.pre_q_sum[slots]

    lanes = state.k_pool.shape[-1]
    if ops.use_prefill_kernel(
            cfg.prefill_impl, nc=nc, window=w, m=m_slot, k_width=cfg.k,
            g=g, d=lanes, itemsize=pdt.itemsize, budget=cfg.vmem_budget):
        # the budget also sizes the attention tile (static: a budget
        # change retraces, mirroring the dispatch decision itself)
        q_block = ops.select_prefill_q_block(
            nc, w, m_slot, cfg.k, g, lanes, itemsize=pdt.itemsize,
            budget=cfg.vmem_budget)
        (out, lm_q_n, lm_v_n, ei_n, ev_n, qs_n, plm_n, pqs_n, kp, vp) = \
            ops.batched_chunk_prefill(
                q, k, v, lm_q_r, lm_v_r, ei_r, ev_r, qs_r, plm_r, pqs_r,
                state.k_pool, state.v_pool, page_table, t0, n_valid,
                n_train, active, window=w, k_width=cfg.k, n_route=s_,
                external_finalize=cfg.external_finalize, q_block=q_block)
        ev_n = ev_n.astype(bool)
    else:
        (out, lm_q_n, lm_v_n, ei_n, ev_n, qs_n, plm_n, pqs_n, kp, vp) = \
            _batched_chunk_prefill_xla(
                state.k_pool, state.v_pool, q, k, v, lm_q_r, lm_v_r, ei_r,
                ev_r, qs_r, plm_r, pqs_r, page_table, t0, n_valid, n_train,
                active, cfg)

    return out.astype(q.dtype), state._replace(
        k_pool=kp, v_pool=vp,
        lm_q=state.lm_q.at[slots].set(lm_q_n),
        lm_v=state.lm_v.at[slots].set(lm_v_n),
        expert_idx=state.expert_idx.at[slots].set(ei_n),
        expert_valid=state.expert_valid.at[slots].set(ev_n),
        q_sum=state.q_sum.at[slots].set(qs_n),
        pre_lm_q=state.pre_lm_q.at[slots].set(plm_n),
        pre_q_sum=state.pre_q_sum.at[slots].set(pqs_n))


def _batched_chunk_prefill_xla(k_pool, v_pool, q, k, v, lm_q_r, lm_v_r,
                               ei_r, ev_r, qs_r, plm_r, pqs_r, page_table,
                               t0, n_valid, n_train, active,
                               cfg: DecodeConfig):
    """XLA path of `mita_batched_chunk_prefill` — the fallback and the
    bit-exact oracle of the fused kernel.  The A-system (training-head)
    and B-system (decode-cache) attention branches are gated behind
    `lax.cond`s on whether any row has prompt / generated positions, so a
    fresh-prompt chunk pays one attention pass, not two; the skipped
    branch's partials are empty (m = -inf, l = 0), which the per-position
    selection discards — bit-identical to computing both."""
    w = cfg.window
    p_rows, hkv, g, nc, d = q.shape
    m_slot = page_table.shape[1]
    ctx = m_slot * w
    scratch = k_pool.shape[0] - 1
    s_ = min(cfg.s, m_slot)
    pdt = k_pool.dtype
    from repro.kernels import ops

    t0 = t0.astype(jnp.int32)
    n_valid = n_valid.astype(jnp.int32)
    n_train = n_train.astype(jnp.int32)
    pos = t0[:, None] + jnp.arange(nc)                  # [P, nc]
    valid = (jnp.arange(nc)[None, :] < n_valid[:, None]) & active[:, None]
    li = jnp.arange(m_slot)                             # landmark ids [M]
    cpos = jnp.arange(ctx)                              # context positions
    m_train, m_a, w_a = _quirk_windows(n_train, w)

    # 1. append chunk KV to the rows' pages (padding/inactive -> scratch).
    # Page ordinal == pos // w, so a token's context index IS its position.
    page_idx = jnp.clip(pos // w, 0, m_slot - 1)
    dst = jnp.where(valid,
                    jnp.take_along_axis(page_table, page_idx, axis=1) * w
                    + pos % w, scratch)
    kp = ops.scatter_pool_rows(k_pool, dst.reshape(-1),
                               jnp.swapaxes(k, 1, 2).reshape(-1, hkv, d))
    vp = ops.scatter_pool_rows(v_pool, dst.reshape(-1),
                               jnp.swapaxes(v, 1, 2).reshape(-1, hkv, d))

    # gathered per-row context in token order; unowned table entries
    # redirect to the scratch row (reads past the valid prefix are masked
    # or zero-weighted below either way)
    owned = (t0 + n_valid + w - 1) // w
    k_ctx = ops.gather_pages(kp, page_table, w, d,
                             owned=owned)              # [P, ctx, Hkv, d]
    v_ctx = ops.gather_pages(vp, page_table, w, d, owned=owned)

    ql32 = jnp.mean(q, axis=2).astype(jnp.float32)      # [P, Hkv, nc, d]

    # 2. B system — the decode cache.  Landmark queries commit as soon as
    # their w-token query window completes; scores/values/expert rows
    # commit once the window's key end exists (ends differ only under the
    # non-aligned n//m quirk, where a prompt landmark's key context extends
    # (i+1)*(w_a - w) tokens past its query window).
    win_b = pos // w
    tok_b = valid[:, None, :] & (win_b[:, None, :] == li[None, :, None])
    sums_b = jnp.einsum("smn,shnd->shmd", tok_b.astype(jnp.float32), ql32)
    m0 = t0 // w
    resume_b = (li[None, :] == m0[:, None]) & (t0 % w != 0)[:, None]
    sums_b = sums_b + jnp.where(resume_b[:, None, :, None],
                                qs_r[:, :, None, :], 0.0)
    q_lm_b = (sums_b / w).astype(lm_q_r.dtype)          # [P, Hkv, M, d]
    wend = (li + 1) * w                                 # [M]
    new_end = t0 + n_valid
    qdone_b = (active[:, None] & (wend[None, :] > t0[:, None])
               & (wend[None, :] <= new_end[:, None]))
    lm_q_s = jnp.where(qdone_b[:, None, :, None], q_lm_b, lm_q_r)

    ends_b = jnp.where(li[None, :] < m_train[:, None],
                       (li[None, :] + 1) * w_a[:, None], wend[None, :])
    s_b = jnp.einsum("schd,shmd->shmc", k_ctx, lm_q_s) / math.sqrt(d)
    vis_b = cpos[None, None, :] < ends_b[:, :, None]
    s_b = jnp.where(vis_b[:, None], s_b.astype(jnp.float32), NEG_INF)
    top_vals, top_loc = jax.lax.top_k(s_b, cfg.k)       # [P, Hkv, M, K]
    new_valid = top_vals > NEG_INF / 2
    ctx_rows = (page_table[:, :, None] * w
                + jnp.arange(w)[None, None, :]).reshape(p_rows, ctx)
    new_rows = jnp.take_along_axis(
        jnp.broadcast_to(ctx_rows[:, None, None, :],
                         (p_rows, hkv, m_slot, ctx)), top_loc, axis=-1)
    p_b = jax.nn.softmax(s_b, axis=-1)
    v_lm_b = jnp.einsum("shmc,schd->shmd", p_b.astype(pdt), v_ctx)
    scommit = (active[:, None] & (ends_b > t0[:, None])
               & (ends_b <= new_end[:, None]))
    sc4 = scommit[:, None, :, None]
    lm_v_s = jnp.where(sc4, v_lm_b.astype(lm_v_r.dtype), lm_v_r)
    ei_s = jnp.where(sc4, new_rows, ei_r)
    ev_s = jnp.where(sc4, new_valid, ev_r)

    # open-window sum == the open row of the sums matrix (the resume
    # contribution already sits inside row m0), selected so the kernel's
    # row-select reproduces it bit-exactly; rows past M mean an exactly
    # full slot, whose open window is empty
    m_new = new_end // w
    q_sum_s = jnp.sum(jnp.where(
        (li[None, :] == m_new[:, None])[:, None, :, None], sums_b, 0.0),
        axis=2)
    q_sum_s = jnp.where(active[:, None, None], q_sum_s, qs_r)

    is_tr = pos < n_train[:, None]
    any_tr = jnp.any(valid & is_tr)
    any_gen = jnp.any(valid & ~is_tr)
    k_ctx_h = jnp.swapaxes(k_ctx, 1, 2)                 # [P, Hkv, ctx, d]
    v_ctx_h = jnp.swapaxes(v_ctx, 1, 2)

    def shared_routed(lm_q_sys, lm_v_sys, avail):
        r = jnp.einsum("shgnd,shmd->shgnm", q, lm_q_sys) / math.sqrt(d)
        r = jnp.where(avail[:, None, None], r.astype(jnp.float32), NEG_INF)
        shared = partial_from_scores(r, lm_v_sys[:, :, None])
        _, e_idx = jax.lax.top_k(r, s_)                 # [P, Hkv, G, nc, s]
        e_ok = jnp.take_along_axis(r, e_idx, axis=-1) > NEG_INF / 2
        return shared, e_idx, e_ok

    def empty_partials(_):
        zo = jnp.zeros((p_rows, hkv, g, nc, d), pdt)
        zm = jnp.full((p_rows, hkv, g, nc), NEG_INF, jnp.float32)
        zl = jnp.zeros((p_rows, hkv, g, nc), jnp.float32)
        return (zo, zm, zl), (zo, zm, zl)

    # 3. A system — the transient prompt-forward landmarks (w_a-pooled).
    # Values/expert tiles are recomputed from the gathered context each
    # chunk (pages are append-only, so the recompute is exact); only the
    # pooled queries and the open-window sum cross chunk boundaries.
    win_a = pos // w_a[:, None]
    tok_a = ((valid & is_tr)[:, None, :]
             & (win_a[:, None, :] == li[None, :, None]))
    sums_a = jnp.einsum("smn,shnd->shmd", tok_a.astype(jnp.float32), ql32)
    m0_a = t0 // w_a
    resume_a = ((li[None, :] == m0_a[:, None])
                & ((t0 % w_a != 0) & (t0 < n_train))[:, None])
    sums_a = sums_a + jnp.where(resume_a[:, None, :, None],
                                pqs_r[:, :, None, :], 0.0)
    q_lm_a = (sums_a / w_a[:, None, None, None].astype(jnp.float32)
              ).astype(plm_r.dtype)
    ends_a = (li[None, :] + 1) * w_a[:, None]           # [P, M]
    qdone_a = (active[:, None] & (ends_a > t0[:, None])
               & (ends_a <= new_end[:, None])
               & (li[None, :] < m_a[:, None]))
    pre_lm_q_s = jnp.where(qdone_a[:, None, :, None], q_lm_a, plm_r)

    open_a = new_end // w_a
    pre_q_sum_s = jnp.sum(jnp.where(
        (li[None, :] == open_a[:, None])[:, None, :, None], sums_a, 0.0),
        axis=2)
    pre_q_sum_s = jnp.where(active[:, None, None], pre_q_sum_s, pqs_r)

    def a_products(_):
        """A-system landmark scores/values/expert locations — the quirk
        build (w_a != w somewhere in the batch)."""
        s_a = jnp.einsum("schd,shmd->shmc", k_ctx, pre_lm_q_s) / math.sqrt(d)
        vis_a = ((cpos[None, None, :] < ends_a[:, :, None])
                 & (li[None, :, None] < m_a[:, None, None]))
        s_a = jnp.where(vis_a[:, None], s_a.astype(jnp.float32), NEG_INF)
        tv_a, tl_a = jax.lax.top_k(s_a, cfg.k)          # [P, Hkv, M, K]
        p_a = jax.nn.softmax(s_a, axis=-1)
        v_lm_a = jnp.einsum("shmc,schd->shmd", p_a.astype(pdt), v_ctx)
        return v_lm_a, tl_a, tv_a > NEG_INF / 2

    def a_reuse(_):
        """All rows window-aligned: the A system IS the B system (same
        pooled queries, same ends), so reuse its products.  Rows at
        landmark ids >= m_a (generated windows) differ, but every read of
        them is availability-masked to an exact-zero contribution."""
        return v_lm_b, top_loc, new_valid

    def a_branches(_):
        """A-system shared/routed partials for prompt positions (skipped
        when the chunk has none)."""
        quirky = jnp.any(active & (n_train % w != 0))
        v_lm_a, tl_a, val_a = jax.lax.cond(quirky, a_products, a_reuse,
                                           None)
        flat_tl = tl_a.reshape(p_rows, hkv, m_slot * cfg.k)
        k_e_a = jnp.take_along_axis(k_ctx_h, flat_tl[..., None], axis=2
                                    ).reshape(p_rows, hkv, m_slot, cfg.k, d)
        v_e_a = jnp.take_along_axis(v_ctx_h, flat_tl[..., None], axis=2
                                    ).reshape(p_rows, hkv, m_slot, cfg.k, d)

        avail_a = ((ends_a[:, None, :] <= pos[:, :, None] + 1)
                   & (li[None, None, :] < m_a[:, None, None])
                   & is_tr[:, :, None])
        shared_a, e_a, eok_a = shared_routed(pre_lm_q_s, v_lm_a, avail_a)
        fe_a = e_a.reshape(p_rows, hkv, g * nc * s_)
        k_sel = jnp.take_along_axis(
            k_e_a.reshape(p_rows, hkv, m_slot, cfg.k * d), fe_a[..., None],
            axis=2).reshape(p_rows, hkv, g, nc, s_ * cfg.k, d)
        v_sel = jnp.take_along_axis(
            v_e_a.reshape(p_rows, hkv, m_slot, cfg.k * d), fe_a[..., None],
            axis=2).reshape(p_rows, hkv, g, nc, s_ * cfg.k, d)
        va_sel = jnp.take_along_axis(
            val_a, fe_a[..., None], axis=2).reshape(p_rows, hkv, g, nc, s_,
                                                    cfg.k)
        lg = jnp.einsum("shgnd,shgnkd->shgnk", q, k_sel) / math.sqrt(d)
        routed_a = partial_from_logits(
            lg, v_sel,
            mask=(va_sel & eok_a[..., None]).reshape(p_rows, hkv, g, nc,
                                                     s_ * cfg.k))
        return ((shared_a.o, shared_a.m, shared_a.l),
                (routed_a.o, routed_a.m, routed_a.l))

    def b_branches(_):
        """B-system shared/routed partials for generated positions — the
        preemption-recompute shape (skipped for fresh-prompt chunks)."""
        off = 0 if cfg.external_finalize else 1
        avail_b = ((wend[None, None, :] <= pos[:, :, None] + off)
                   & ~is_tr[:, :, None])
        shared_b, e_b, eok_b = shared_routed(lm_q_s, lm_v_s.astype(pdt),
                                             avail_b)
        fe_b = e_b.reshape(p_rows, hkv, g * nc * s_)
        rows_b = jnp.take_along_axis(ei_s, fe_b[..., None], axis=2)
        rv_b = jnp.take_along_axis(ev_s, fe_b[..., None], axis=2)
        k_sel = ops.gather_pool_rows(
            kp, rows_b.reshape(p_rows, hkv, -1), d).reshape(
            p_rows, hkv, g, nc, s_ * cfg.k, d)
        v_sel = ops.gather_pool_rows(
            vp, rows_b.reshape(p_rows, hkv, -1), d).reshape(
            p_rows, hkv, g, nc, s_ * cfg.k, d)
        lg = jnp.einsum("shgnd,shgnkd->shgnk", q, k_sel) / math.sqrt(d)
        routed_b = partial_from_logits(
            lg, v_sel,
            mask=(rv_b.reshape(p_rows, hkv, g, nc, s_, cfg.k)
                  & eok_b[..., None]).reshape(p_rows, hkv, g, nc,
                                              s_ * cfg.k))
        return ((shared_b.o, shared_b.m, shared_b.l),
                (routed_b.o, routed_b.m, routed_b.l))

    sh_a, ro_a = jax.lax.cond(any_tr, a_branches, empty_partials, None)
    sh_b, ro_b = jax.lax.cond(any_gen, b_branches, empty_partials, None)

    # local: each position attends its own window [start, pos] (w_a-sized
    # inside the prompt, w-sized outside; w_a <= 2w - 1, so a 2w-wide
    # per-position gather from the context covers both)
    lw = 2 * w
    start = jnp.where(is_tr, win_a * w_a[:, None], (pos // w) * w)
    loc_pos = start[:, :, None] + jnp.arange(lw)[None, None, :]  # [P,nc,2w]
    loc_idx = jnp.clip(loc_pos, 0, ctx - 1)
    k_loc = jnp.take_along_axis(
        k_ctx_h, loc_idx.reshape(p_rows, 1, nc * lw, 1),
        axis=2).reshape(p_rows, hkv, nc, lw, d)
    v_loc = jnp.take_along_axis(
        v_ctx_h, loc_idx.reshape(p_rows, 1, nc * lw, 1),
        axis=2).reshape(p_rows, hkv, nc, lw, d)
    s_loc = jnp.einsum("shgnd,shnwd->shgnw", q, k_loc) / math.sqrt(d)
    local = partial_from_logits(
        s_loc, v_loc[:, :, None],
        mask=(loc_pos <= pos[:, :, None])[:, None, None])

    sel = is_tr[:, None, None, :]                       # over [P, H, G, nc]

    def pick(pa, pb):
        return Partial(o=jnp.where(sel[..., None], pa[0], pb[0]),
                       m=jnp.where(sel, pa[1], pb[1]),
                       l=jnp.where(sel, pa[2], pb[2]))

    out = combine([pick(sh_a, sh_b), pick(ro_a, ro_b), local])
    out = jnp.where(active[:, None, None, None, None], out, 0.0
                    ).astype(q.dtype)
    return (out, lm_q_s, lm_v_s, ei_s, ev_s, q_sum_s, pre_lm_q_s,
            pre_q_sum_s, kp, vp)
