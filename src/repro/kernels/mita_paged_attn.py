"""Fused paged-decode MiTA attention kernel (TPU Pallas; interpret on CPU).

One decode step of the serving engine's paged cache, per (slot, KV head)
program, without ever materializing a contiguous per-slot cache:

  * **append** — the new (k, v) row is DMA'd straight into the slot's
    current page (`page_table[s, t//w] * w + t%w`; scratch row for inactive
    slots), with the pool aliased as an output so the write is in place;
  * **local window** — the current page's `w` rows are DMA'd HBM→VMEM in
    token order and the just-appended position is patched from registers
    (the read may race the append on-chip; the patch makes it exact);
  * **shared landmarks** — `lm_q`/`lm_v` arrive as per-slot VMEM blocks;
    routing logits double as the shared-expert branch scores;
  * **routed experts** — the top-`s` experts per query head are selected
    in-kernel from the routing logits, and their stored GLOBAL pool rows
    (`expert_idx`, assigned at finalize time) are gathered row-by-row via
    DMA — the vLLM-style page walk, fused with the attention that consumes
    it.  DMA addresses are scalars: each selected expert's ordinal reaches
    SMEM through a reduction, and its K row ids are DMA'd HBM→SMEM.

The three branches merge in-kernel with the same guarded online-softmax as
`repro.core.combine`, so the output equals one softmax over the union of
all branch keys (paper Alg. 1 line 16).  The XLA gather path in
`core.mita_decode.mita_paged_decode_step` is the oracle
(`tests/test_kernel_oracle.py` pins parity over randomized page
permutations, ragged per-slot progress, and inactive slots).

The pools must be 32-bit, with head rows of whole 128-lane tiles on the
TPU (`kernels.ops.pool_lanes`): Mosaic DMAs one KV head's row of a
``[R, Hkv, L]`` pool only then; the kernels read its first ``d`` lanes.

Per-program VMEM working set (budget-checked by `kernels.ops` before
dispatch): q/out `2·G·d`, landmark tiles `2·M·d`, local page `2·w·d`, one
expert KV tile `2·K·d`, plus the `M·K` expert bias table.  The
expert-row gathers are double-buffered by default (row i+1's copies are in
flight while row i's drain — the decode step is DMA-latency bound, not
bandwidth bound); ``REPRO_DMA_PIPELINE=0`` serializes them for debugging
(`tests/test_kernel_oracle.py` pins parity in both modes).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float(jnp.finfo(jnp.float32).min)


def _merge(m_a, l_a, o_a, m_b, l_b, o_b):
    """Online-softmax merge of two partials ([G] stats, [G, d] values)."""
    m_n = jnp.maximum(m_a, m_b)
    safe = jnp.where(m_n == NEG_INF, 0.0, m_n)
    sa = jnp.exp(jnp.where(m_a == NEG_INF, NEG_INF, m_a - safe))
    sb = jnp.exp(jnp.where(m_b == NEG_INF, NEG_INF, m_b - safe))
    return (m_n, l_a * sa + l_b * sb,
            o_a * sa[:, None] + o_b * sb[:, None])


def _partial(s):
    """[G, n] masked scores -> (m [G], l [G], p [G, n]) with empty-row guard."""
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - jnp.where(m == NEG_INF, 0.0, m)[:, None])
    p = jnp.where(s == NEG_INF, 0.0, p)
    return m, jnp.sum(p, axis=-1), p


def _paged_kernel(pt_ref, t_ref, act_ref, mcnt_ref,              # SMEM
                  q_ref, kn_hbm, vn_hbm, lmq_ref, lmv_ref,
                  eb_ref, ei_hbm, kpool_ref, vpool_ref,          # ANY
                  o_ref, kpout_ref, vpout_ref,
                  knew, vnew, kloc, vloc, ketile, vetile, e_sm, rows_sm,
                  sem, psem,
                  *, window: int, n_route: int, fuse_append: bool,
                  pipeline: bool, scale: float):
    s = pl.program_id(0)
    h = pl.program_id(1)
    w = window
    ts = t_ref[s]
    act = act_ref[s] == 1
    mc = mcnt_ref[s]
    n_rows = kpout_ref.shape[0]
    cur = pt_ref[s, ts // w]
    page0 = pl.multiple_of(cur * w, w)
    # inactive slots append to the trailing scratch row (never read back)
    row_new = jnp.where(act, page0 + ts % w, n_rows - 1)

    # the new row HBM->VMEM (register patch below) and, when fused,
    # HBM->HBM straight into its pool row
    for src, dst in ((kn_hbm, knew), (vn_hbm, vnew)):
        cp = pltpu.make_async_copy(src.at[s, pl.ds(h, 1)], dst, sem)
        cp.start()
        cp.wait()
    if fuse_append:
        for src, dst in ((kn_hbm, kpout_ref), (vn_hbm, vpout_ref)):
            cp = pltpu.make_async_copy(src.at[s, pl.ds(h, 1)],
                                       dst.at[row_new, pl.ds(h, 1)], sem)
            cp.start()
            cp.wait()

    # local page HBM->VMEM in token order; the appended position is patched
    # from registers so the result never depends on append/read ordering
    cp = pltpu.make_async_copy(kpool_ref.at[pl.ds(page0, w), h], kloc, sem)
    cp.start()
    cp.wait()
    cp = pltpu.make_async_copy(vpool_ref.at[pl.ds(page0, w), h], vloc, sem)
    cp.start()
    cp.wait()
    q = q_ref[0, 0].astype(jnp.float32) * scale              # [G, d]
    g, d = q.shape
    # pool rows are lane-padded past the head dim (`ops.pool_lanes`)
    own = jax.lax.broadcasted_iota(jnp.int32, (w, 1), 0) == ts % w
    k_loc = jnp.where(own, knew[...].astype(jnp.float32),
                      kloc[...].astype(jnp.float32))[:, :d]   # [w, d]
    v_loc = jnp.where(own, vnew[...].astype(jnp.float32),
                      vloc[...].astype(jnp.float32))[:, :d]

    m_slot = lmq_ref.shape[2]
    k_width = ketile.shape[0]

    # shared-landmark branch; r doubles as the routing logits
    lmq = lmq_ref[0, 0].astype(jnp.float32)                  # [M, d]
    r = jax.lax.dot_general(q, lmq, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    lm_ids = jax.lax.broadcasted_iota(jnp.int32, (g, m_slot), 1)
    r = jnp.where(lm_ids < mc, r, NEG_INF)
    m_acc, l_acc, p1 = _partial(r)
    o_acc = jax.lax.dot_general(p1, lmv_ref[0, 0].astype(jnp.float32),
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)

    # local-window branch: the slot's own page, positions <= t
    s_loc = jax.lax.dot_general(q, k_loc, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
    loc_ids = jax.lax.broadcasted_iota(jnp.int32, (g, w), 1)
    s_loc = jnp.where(loc_ids <= ts % w, s_loc, NEG_INF)
    m_l, l_l, p2 = _partial(s_loc)
    o_l = jax.lax.dot_general(p2, v_loc, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    m_acc, l_acc, o_acc = _merge(m_acc, l_acc, o_acc, m_l, l_l, o_l)

    # routed experts: top-s of r per query head (first index of the max,
    # the lax.top_k tie rule), expert rows gathered from the pool by their
    # stored GLOBAL row ids — no page-table lookup needed.  DMA addresses
    # must be scalars: the expert ordinal goes vector -> SMEM through a
    # full reduction, and the expert's K row ids are DMA'd HBM -> SMEM.
    g_ids = jax.lax.broadcasted_iota(jnp.int32, (g, 1), 0)
    r_route = r
    for _ in range(n_route):
        mx = jnp.max(r_route, axis=-1, keepdims=True)        # [G, 1]
        e_j = jnp.min(jnp.where(r_route == mx, lm_ids, m_slot), axis=-1,
                      keepdims=True)                         # [G, 1]
        ok_j = mx > NEG_INF / 2
        r_route = jnp.where(lm_ids == e_j, NEG_INF, r_route)
        for gi in range(g):
            e_sm[gi] = jnp.sum(jnp.where(g_ids == gi, e_j, 0))

        m_rows, l_rows, o_rows = [], [], []
        for gi in range(g):
            e_gi = jnp.minimum(e_sm[gi], m_slot - 1)
            cp = pltpu.make_async_copy(ei_hbm.at[s, h, e_gi], rows_sm, sem)
            cp.start()
            cp.wait()
            bias = eb_ref[0, 0, pl.ds(e_gi, 1), :]           # [1, K]

            def row_copies(kk, slot):
                row = rows_sm[kk]
                return (pltpu.make_async_copy(kpool_ref.at[pl.ds(row, 1), h],
                                              ketile.at[pl.ds(kk, 1)],
                                              psem.at[slot, 0]),
                        pltpu.make_async_copy(vpool_ref.at[pl.ds(row, 1), h],
                                              vetile.at[pl.ds(kk, 1)],
                                              psem.at[slot, 1]))

            if pipeline:
                # double-buffered row walk: row kk+1's copies are in
                # flight while row kk's drain (distinct destination rows,
                # alternating semaphore pairs) — hides the per-row DMA
                # latency the serial walk pays K times
                ck, cv = row_copies(0, 0)
                ck.start()
                cv.start()

                def gather_row(kk, _):
                    @pl.when(kk + 1 < k_width)
                    def _():
                        nk, nv = row_copies(kk + 1, (kk + 1) % 2)
                        nk.start()
                        nv.start()
                    wk, wv = row_copies(kk, kk % 2)
                    wk.wait()
                    wv.wait()
                    return 0
            else:
                def gather_row(kk, _):
                    ck, cv = row_copies(kk, 0)
                    ck.start()
                    ck.wait()
                    cv.start()
                    cv.wait()
                    return 0

            jax.lax.fori_loop(0, k_width, gather_row, 0)
            s_e = jax.lax.dot_general(
                q[gi:gi + 1], ketile[...].astype(jnp.float32)[:, :d],
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)          # [1, K]
            s_e = s_e + bias
            s_e = jnp.where(ok_j[gi:gi + 1], s_e, NEG_INF)
            m_e, l_e, p_e = _partial(s_e)
            o_e = jax.lax.dot_general(p_e,
                                      vetile[...].astype(jnp.float32)[:, :d],
                                      (((1,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
            m_rows.append(m_e)
            l_rows.append(l_e)
            o_rows.append(o_e)
        m_acc, l_acc, o_acc = _merge(
            m_acc, l_acc, o_acc, jnp.concatenate(m_rows),
            jnp.concatenate(l_rows), jnp.concatenate(o_rows))

    denom = jnp.where(l_acc == 0.0, 1.0, l_acc)
    out = o_acc / denom[:, None]
    out = jnp.where((l_acc != 0.0)[:, None] & act, out, 0.0)
    o_ref[0, 0] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("window", "n_route", "fuse_append", "pipeline",
                     "vmem_limit", "interpret"))
def mita_paged_attention(q: jax.Array, k_new: jax.Array, v_new: jax.Array,
                         lm_q: jax.Array, lm_v: jax.Array,
                         expert_idx: jax.Array, expert_valid: jax.Array,
                         k_pool: jax.Array, v_pool: jax.Array,
                         page_table: jax.Array, t: jax.Array,
                         active: jax.Array, m_cnt: jax.Array,
                         window: int, n_route: int = 1,
                         fuse_append: bool = True, pipeline: bool = True,
                         vmem_limit: int = 0, interpret: bool = False):
    """Fused paged-decode attention (+ optional in-place KV append).

    q: [S, Hkv, G, d]; k_new/v_new: [S, Hkv, d];
    lm_q/lm_v: [S, Hkv, M, d]; expert_idx: [S, Hkv, M, K] GLOBAL pool rows;
    expert_valid: [S, Hkv, M, K] bool; k_pool/v_pool: [R + 1, Hkv, L]
    32-bit, ``L >= d`` lanes per head row (`kernels.ops.pool_lanes`; row R
    is the inactive-slot write scratch); page_table: [S, M] int32;
    t: [S] int32 tokens already cached; active: [S] bool;
    m_cnt: [S] int32 landmarks visible to this step (t//w external-finalize,
    (t+1)//w inline — the caller decides).

    Returns (out [S, Hkv, G, d] in q's dtype, k_pool, v_pool).  The pools
    are aliased in/out; with ``fuse_append`` the new row is written at
    ``page_table[s, t//w]*w + t%w`` (scratch row when inactive), otherwise
    they pass through untouched (the caller already appended, e.g. before
    an inline finalize).
    """
    n_slots, hkv, g, d = q.shape
    m_slot, k_width = expert_idx.shape[-2:]
    lanes = k_pool.shape[-1]
    pad = ((0, 0), (0, 0), (0, lanes - d))
    bias = jnp.where(expert_valid, 0.0, NEG_INF).astype(jnp.float32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n_slots, hkv),
        in_specs=[
            pl.BlockSpec((1, 1, g, d), lambda s, h, *_: (s, h, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),      # k_new (HBM)
            pl.BlockSpec(memory_space=pl.ANY),      # v_new (HBM)
            pl.BlockSpec((1, 1, m_slot, d), lambda s, h, *_: (s, h, 0, 0)),
            pl.BlockSpec((1, 1, m_slot, d), lambda s, h, *_: (s, h, 0, 0)),
            pl.BlockSpec((1, 1, m_slot, k_width),
                         lambda s, h, *_: (s, h, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),      # expert_idx (HBM)
            pl.BlockSpec(memory_space=pl.ANY),      # k_pool (HBM)
            pl.BlockSpec(memory_space=pl.ANY),      # v_pool (HBM)
        ],
        out_specs=[
            pl.BlockSpec((1, 1, g, d), lambda s, h, *_: (s, h, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, lanes), k_pool.dtype),
            pltpu.VMEM((1, lanes), v_pool.dtype),
            pltpu.VMEM((window, lanes), k_pool.dtype),
            pltpu.VMEM((window, lanes), v_pool.dtype),
            pltpu.VMEM((k_width, lanes), k_pool.dtype),
            pltpu.VMEM((k_width, lanes), v_pool.dtype),
            pltpu.SMEM((g,), jnp.int32),           # routed expert ordinals
            pltpu.SMEM((k_width,), jnp.int32),     # one expert's row ids
            pltpu.SemaphoreType.DMA(()),
            pltpu.SemaphoreType.DMA((2, 2)),   # expert-row pipeline pairs
        ],
    )
    kern = functools.partial(_paged_kernel, window=window, n_route=n_route,
                             fuse_append=fuse_append, pipeline=pipeline,
                             scale=1.0 / math.sqrt(d))
    out, kp, vp = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((n_slots, hkv, g, d), q.dtype),
            jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
            jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype),
        ],
        # operand indices count the 4 scalar-prefetch args
        input_output_aliases={11: 1, 12: 2},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit or None),
        interpret=interpret,
        name="mita_paged_attention",
    )(page_table.astype(jnp.int32), t.astype(jnp.int32),
      active.astype(jnp.int32), m_cnt.astype(jnp.int32),
      q, jnp.pad(k_new.astype(k_pool.dtype), pad),
      jnp.pad(v_new.astype(v_pool.dtype), pad),
      lm_q, lm_v, bias, expert_idx.astype(jnp.int32), k_pool, v_pool)
    return out, kp, vp
