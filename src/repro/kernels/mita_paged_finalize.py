"""Fused paged landmark-finalize MiTA kernel (TPU Pallas; interpret on CPU).

Every ``window`` decoded tokens a slot's open window completes: its pooled
query becomes a landmark row and the landmark scores a fresh top-k expert
gather over the slot's whole context.  This was the last decode-path op
still on the XLA gathers (`core.mita_decode._paged_finalize`).  Per
(slot, KV-head) program:

  * **context gather** — DMAs the slot's page set HBM→VMEM in token order
    (pages named by the SMEM page table; unowned table entries DMA junk
    that the visibility mask cancels exactly — every lane at or past
    ``t_new`` scores NEG_INF, so its softmax weight underflows to an exact
    0.0 and 0·junk == 0 bit-exactly);
  * **landmark pool** — divides the accumulated window query sum by ``w``
    (the same op the oracle runs on the same f32 accumulator);
  * **expert rebuild** — one in-kernel top-k over the masked landmark
    scores, context positions mapped to GLOBAL pool rows through the page
    table with one exact select per table entry, landmark value via the
    in-kernel softmax replica;
  * **commit** — merges the new landmark/expert rows at window ordinal
    ``t_new // w - 1`` for ``due`` slots only and zeroes their q_sum;
    non-due (and inactive) slots pass through bit-exactly.

The XLA path in `core.mita_decode._paged_finalize` stays as the fallback
and the bit-exact oracle (f32 pools): `tests/test_kernel_oracle.py` pins
lm_q/lm_v/expert rows/validity/q_sum bit-identical over shuffled page
tables, ragged per-slot t, and inactive slots.

The pools must be 32-bit (see `kernels.mita_paged_attn`).  Per-program
VMEM working set: `kernels.ops.paged_finalize_vmem_bytes` — dominated by
the gathered context and its f32 working copy.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mita_chunk_prefill import (NEG_INF, _ctx_to_rows, _dot,
                                              _softmax, _topk)


def _finalize_kernel(pt_ref, t_ref, due_ref,                     # SMEM
                     qs_ref, lmq_ref, lmv_ref, ei_ref, ev_ref,
                     kpool_ref, vpool_ref,
                     lmq_o, lmv_o, ei_o, ev_o, qs_o,
                     kctx, vctx, sem,
                     *, window: int, k_width: int):
    s = pl.program_id(0)
    h = pl.program_id(1)
    w = window
    m_slot = lmq_ref.shape[2]
    d = lmq_ref.shape[3]
    ctx = m_slot * w

    t_new = t_ref[s]
    due = due_ref[s] == 1

    # ---- 1. gather the slot's context (token order) ----
    def gather_page(mi, _):
        page = pt_ref[s, mi]
        base = pl.multiple_of(page * w, w)
        ck = pltpu.make_async_copy(kpool_ref.at[pl.ds(base, w), h],
                                   kctx.at[pl.ds(mi * w, w)], sem)
        ck.start()
        ck.wait()
        cv = pltpu.make_async_copy(vpool_ref.at[pl.ds(base, w), h],
                                   vctx.at[pl.ds(mi * w, w)], sem)
        cv.start()
        cv.wait()
        return 0

    jax.lax.fori_loop(0, m_slot, gather_page, 0)

    # pool rows are lane-padded past the head dim (`ops.pool_lanes`)
    k_ctx = kctx[...].astype(jnp.float32)[:, :d]        # [ctx, d]
    v_ctx = vctx[...].astype(jnp.float32)[:, :d]

    # ---- 2. pool the completed window's queries into the landmark ----
    q_lm = (qs_ref[0, 0] / w).astype(lmq_ref.dtype)     # [1, d]

    # ---- 3. rebuild the top-k expert gather over the visible context ----
    scores = _dot(q_lm.astype(jnp.float32), k_ctx) / math.sqrt(d)
    cid = jax.lax.broadcasted_iota(jnp.int32, (1, ctx), 1)
    scores = jnp.where(cid < t_new, scores, NEG_INF)    # [1, ctx]
    top_vals, top_loc = _topk(scores, k_width)          # [1, K]
    valid = (top_vals > NEG_INF / 2).astype(jnp.int32)  # [1, K]
    rows = _ctx_to_rows(top_loc, pt_ref, s, m_slot, w)  # [1, K] global rows
    p = _softmax(scores)                                # [1, ctx]
    v_lm = jax.lax.dot_general(p, v_ctx, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32
                               ).astype(lmv_ref.dtype)  # [1, d]

    # ---- 4. commit at window ordinal t_new//w - 1 for due slots ----
    i = t_new // w - 1
    li = jax.lax.broadcasted_iota(jnp.int32, (m_slot, 1), 0)
    sel = due & (li == i)                               # [M, 1]
    lmq_o[0, 0] = jnp.where(sel, q_lm, lmq_ref[0, 0])
    lmv_o[0, 0] = jnp.where(sel, v_lm, lmv_ref[0, 0])
    ei_o[0, 0] = jnp.where(sel, rows, ei_ref[0, 0])
    ev_o[0, 0] = jnp.where(sel, valid, ev_ref[0, 0])
    qs_o[0, 0] = jnp.where(due, 0.0, qs_ref[0, 0])


@functools.partial(
    jax.jit, static_argnames=("window", "k_width", "vmem_limit", "interpret"))
def mita_paged_finalize_fused(q_sum, lm_q, lm_v, expert_idx, expert_valid,
                              k_pool, v_pool, page_table, t_new, due,
                              window: int, k_width: int,
                              vmem_limit: int = 0, interpret: bool = False):
    """Fused paged landmark finalize.

    q_sum: [S, Hkv, d] f32; lm_q/lm_v: [S, Hkv, M, d]; expert_idx:
    [S, Hkv, M, K] GLOBAL pool rows; expert_valid: [S, Hkv, M, K] bool;
    k_pool/v_pool: [R + 1, Hkv, L] 32-bit, ``L >= d`` lanes per head row
    (`kernels.ops.pool_lanes`; read-only here — finalize never
    writes the pools); page_table: [S, M] i32; t_new: [S] i32 (per-slot
    position AFTER the step); due: [S] bool.

    Returns (lm_q, lm_v, expert_idx, expert_valid [i32], q_sum) with
    non-due rows passed through bit-exactly.  See
    `core.mita_decode._paged_finalize` for the semantics this kernel must
    (and is pinned to) reproduce.
    """
    n_slots, hkv, m_slot, d = lm_q.shape
    kw = expert_idx.shape[-1]
    assert kw == k_width
    pdt = k_pool.dtype

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n_slots, hkv),
        in_specs=[
            pl.BlockSpec((1, 1, 1, d), lambda s, h, *_: (s, h, 0, 0)),
            pl.BlockSpec((1, 1, m_slot, d), lambda s, h, *_: (s, h, 0, 0)),
            pl.BlockSpec((1, 1, m_slot, d), lambda s, h, *_: (s, h, 0, 0)),
            pl.BlockSpec((1, 1, m_slot, kw), lambda s, h, *_: (s, h, 0, 0)),
            pl.BlockSpec((1, 1, m_slot, kw), lambda s, h, *_: (s, h, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),      # k_pool (HBM)
            pl.BlockSpec(memory_space=pl.ANY),      # v_pool (HBM)
        ],
        out_specs=[
            pl.BlockSpec((1, 1, m_slot, d), lambda s, h, *_: (s, h, 0, 0)),
            pl.BlockSpec((1, 1, m_slot, d), lambda s, h, *_: (s, h, 0, 0)),
            pl.BlockSpec((1, 1, m_slot, kw), lambda s, h, *_: (s, h, 0, 0)),
            pl.BlockSpec((1, 1, m_slot, kw), lambda s, h, *_: (s, h, 0, 0)),
            pl.BlockSpec((1, 1, 1, d), lambda s, h, *_: (s, h, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((m_slot * window, k_pool.shape[-1]), pdt),
            pltpu.VMEM((m_slot * window, k_pool.shape[-1]), pdt),
            pltpu.SemaphoreType.DMA(()),
        ],
    )
    kern = functools.partial(_finalize_kernel, window=window,
                             k_width=k_width)
    lmq, lmv, ei, ev, qs = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(lm_q.shape, lm_q.dtype),
            jax.ShapeDtypeStruct(lm_v.shape, lm_v.dtype),
            jax.ShapeDtypeStruct(expert_idx.shape, jnp.int32),
            jax.ShapeDtypeStruct(expert_valid.shape, jnp.int32),
            jax.ShapeDtypeStruct(q_sum[:, :, None].shape, jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit or None),
        interpret=interpret,
        name="mita_paged_finalize_fused",
    )(page_table.astype(jnp.int32), t_new.astype(jnp.int32),
      due.astype(jnp.int32),
      q_sum[:, :, None], lm_q, lm_v, expert_idx.astype(jnp.int32),
      expert_valid.astype(jnp.int32), k_pool, v_pool)
    return lmq, lmv, ei, ev, qs[:, :, 0]
