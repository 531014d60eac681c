"""Fused batched chunk-prefill MiTA kernel (TPU Pallas; interpret on CPU).

One window-aligned prefill chunk for EVERY active slot, per (slot, KV-head)
program — the arithmetic-dense prefill counterpart of the paged-decode
kernel (`mita_paged_attn.py`).  Each program:

  * **append** — DMAs the chunk's valid K/V rows straight into the slot's
    pages (``page_table[s, pos // w] * w + pos % w``; scratch row for
    padding and inactive rows), pools aliased in/out so the write is in
    place;
  * **context gather** — DMAs the slot's whole page set HBM→VMEM in token
    order (context index == token position) and patches the just-appended
    rows from registers, so every downstream read is append-order exact;
  * **landmark build** — resumes the open-window query sums (both the
    decode cache's w-sized windows and the training head's n//m-sized
    prompt windows, `core.mita_decode.mita_batched_chunk_prefill`'s A/B
    systems), scores each completed window against the gathered context
    with one in-kernel top-k, and commits landmark queries/values + global
    expert rows exactly where the XLA oracle does;
  * **chunk attention** — tiles of ``q_block`` windows of one query head.
    Every expert key is a position of the gathered context, so one
    ``[tile, ctx]`` score matrix serves the routed AND the local branch:
    each context lane carries a weight — how many of the row's routed
    experts hold that position (an exact 0/1 matmul of the routing
    one-hots against a per-landmark membership table) plus one if it lies
    in the row's local window — and ONE softmax runs over those weighted
    lanes and the shared-landmark scores (A/B system selected per
    position).  A key that two branches both hold counts twice, exactly
    as in the oracle's concatenated-branch softmax.

The XLA path in `core.mita_decode.mita_batched_chunk_prefill` is the
fallback and the oracle: `tests/test_kernel_oracle.py` pins pages,
landmarks, expert rows, and the resumed q_sum state bit-identical (f32
pools) across ragged resume points, non-aligned heads, preemption
recompute, and inactive slots; outputs agree within f32 rounding.

The pools must be 32-bit: Mosaic DMAs one KV head's row of a
``[R, Hkv, d]`` pool only when a row is a whole number of 32-bit words
per head.  Per-program VMEM working set: `kernels.ops.
chunk_prefill_vmem_bytes`.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float(jnp.finfo(jnp.float32).min)


def _first_argmax(x):
    """Row-wise (max, first-index-of-max) of [R, C] as [R, 1] columns —
    the lax.top_k / jnp.argmax tie rule, expressed as two vector
    reduces."""
    c = x.shape[-1]
    cid = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    mx = jnp.max(x, axis=-1, keepdims=True)
    ix = jnp.min(jnp.where(x == mx, cid, c), axis=-1, keepdims=True)
    return mx, ix


def _topk(x, k: int):
    """Iterative top-k over the last axis of [R, C]; bit-identical values
    and indices to `jax.lax.top_k` (descending, ties by ascending index).
    Selected lanes are retired with -inf, strictly below the NEG_INF used
    for masking, so duplicates of NEG_INF still come out in index order.
    Returns ([R, k] values, [R, k] int32 indices)."""
    r, c = x.shape
    cid = jax.lax.broadcasted_iota(jnp.int32, (r, c), 1)
    kid = jax.lax.broadcasted_iota(jnp.int32, (r, k), 1)

    def body(j, carry):
        x, vals, idxs = carry
        mx, ix = _first_argmax(x)
        vals = jnp.where(kid == j, mx, vals)
        idxs = jnp.where(kid == j, ix, idxs)
        return jnp.where(cid == ix, -jnp.inf, x), vals, idxs

    _, vals, idxs = jax.lax.fori_loop(
        0, k, body, (x, jnp.zeros((r, k), x.dtype),
                     jnp.zeros((r, k), jnp.int32)))
    return vals, idxs


def _ctx_to_rows(loc, pt_ref, s, m_slot: int, w: int):
    """Context positions [R, C] -> GLOBAL pool rows through the slot's
    page table: ``pt[loc // w] * w + loc % w`` (page lookup as M exact
    selects — SMEM scalars broadcast into lanes)."""
    pg = loc // w
    page = jnp.zeros_like(loc)
    for j in range(m_slot):
        page = jnp.where(pg == j, pt_ref[s, j], page)
    return page * w + loc % w


def _rows_to_ctx(rows, pt_ref, s, m_slot: int, w: int):
    """GLOBAL pool rows [R, C] -> context positions through the slot's
    page table (first matching table entry); rows on no page of the slot
    map to ``m_slot * w``, a lane no context holds."""
    page = rows // w
    ordn = jnp.full_like(rows, m_slot)
    for j in reversed(range(m_slot)):
        ordn = jnp.where(page == pt_ref[s, j], j, ordn)
    return jnp.where(ordn < m_slot, ordn * w + rows % w, m_slot * w)


def _membership(loc, ok, ctx: int):
    """[M, ctx] f32 table: 1.0 where landmark row i's expert holds
    context position c (entries with ``ok`` False, or out of range,
    hold nothing).  loc/ok: [M, K]; positions within a row are distinct
    (they come from one top-k)."""
    m, k = loc.shape
    cid = jax.lax.broadcasted_iota(jnp.int32, (m, ctx), 1)
    kid = jax.lax.broadcasted_iota(jnp.int32, (m, k), 1)
    loc = jnp.where(ok, loc, ctx)

    def body(j, mem):
        col = jnp.sum(jnp.where(kid == j, loc, 0), axis=-1, keepdims=True)
        return jnp.where(cid == col, 1.0, mem)

    return jax.lax.fori_loop(0, k, body, jnp.zeros((m, ctx), jnp.float32))


def _dot(a, b):
    """[R, d] x [C, d] -> [R, C] f32 contraction over the trailing dim."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _mm(a, b):
    """[R, C] x [C, d] -> [R, d] f32 matmul."""
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _softmax(x):
    """Replicates jax.nn.softmax(x, axis=-1) op-for-op (bit-parity with
    the XLA oracle's landmark-value softmax)."""
    mx = jnp.max(x, axis=-1, keepdims=True)
    un = jnp.exp(x - mx)
    return un / jnp.sum(un, axis=-1, keepdims=True)


def _chunk_kernel(pt_ref, t0_ref, nv_ref, ntr_ref, act_ref,      # SMEM
                  q_ref, k_hbm, v_hbm, lmq_ref, lmv_ref, ei_ref, ev_ref,
                  qs_ref, plmq_ref, pqs_ref, kpool_ref, vpool_ref,
                  o_ref, lmq_o, lmv_o, ei_o, ev_o, qs_o, plmq_o, pqs_o,
                  kp_o, vp_o,
                  kctx, vctx, mem_a, mem_b, sem,
                  *, window: int, k_width: int, n_route: int,
                  external: bool, q_block: int):
    s = pl.program_id(0)
    h = pl.program_id(1)
    w = window
    nc = q_ref.shape[3]
    m_slot = lmq_ref.shape[2]
    g = q_ref.shape[2]
    d = q_ref.shape[4]
    ctx = m_slot * w
    n_rows = kp_o.shape[0]

    t0 = t0_ref[s]
    nv = nv_ref[s]
    ntr = ntr_ref[s]
    act = act_ref[s] == 1
    new_end = t0 + nv
    m_train = ntr // w
    m_a = jnp.maximum(m_train, 1)
    w_a = jnp.maximum(ntr // m_a, 1)

    # ---- 1. append the chunk's rows to the slot's pages (in place) ----
    def append_row(n, _):
        posn = t0 + n
        page = pt_ref[s, jnp.clip(posn // w, 0, m_slot - 1)]
        row = jnp.where(act & (n < nv), page * w + posn % w, n_rows - 1)
        for src, dst in ((k_hbm, kp_o), (v_hbm, vp_o)):
            cp = pltpu.make_async_copy(src.at[s, h, pl.ds(n, 1)],
                                       dst.at[pl.ds(row, 1), h], sem)
            cp.start()
            cp.wait()
        return 0

    jax.lax.fori_loop(0, nc, append_row, 0)

    # ---- 2. gather the slot's context (token order), patch own rows ----
    def gather_page(mi, _):
        base = pl.multiple_of(pt_ref[s, mi] * w, w)
        for src, dst in ((kp_o, kctx), (vp_o, vctx)):
            cp = pltpu.make_async_copy(src.at[pl.ds(base, w), h],
                                       dst.at[pl.ds(mi * w, w)], sem)
            cp.start()
            cp.wait()
        return 0

    jax.lax.fori_loop(0, m_slot, gather_page, 0)

    def patch_row(n, _):
        @pl.when(act & (n < nv))
        def _():
            for src, dst in ((k_hbm, kctx), (v_hbm, vctx)):
                cp = pltpu.make_async_copy(src.at[s, h, pl.ds(n, 1)],
                                           dst.at[pl.ds(t0 + n, 1)], sem)
                cp.start()
                cp.wait()
        return 0

    jax.lax.fori_loop(0, nc, patch_row, 0)

    # pool rows are lane-padded past the head dim (`ops.pool_lanes`)
    k_ctx = kctx[...].astype(jnp.float32)[:, :d]        # [ctx, d]
    v_ctx = vctx[...].astype(jnp.float32)[:, :d]
    q = q_ref[0, 0].astype(jnp.float32)                 # [G, nc, d]
    ql = jnp.mean(q, axis=0)                            # [nc, d] group pool

    nid = jax.lax.broadcasted_iota(jnp.int32, (m_slot, nc), 1)
    lid = jax.lax.broadcasted_iota(jnp.int32, (m_slot, nc), 0)
    valid_n = act & (nid < nv)                          # [M, nc]
    li = jax.lax.broadcasted_iota(jnp.int32, (m_slot, 1), 0)  # landmark ids
    cid = jax.lax.broadcasted_iota(jnp.int32, (m_slot, ctx), 1)

    # ---- 3. B system: the decode cache (w-sized windows) ----
    win_b = (t0 + nid) // w
    tok_b = (valid_n & (win_b == lid)).astype(jnp.float32)
    sums_b = _mm(tok_b, ql)                             # [M, d]
    m0 = t0 // w
    resume_b = (li == m0) & (t0 % w != 0)
    sums_b = sums_b + jnp.where(resume_b, qs_ref[0, 0], 0.0)
    q_lm_b = (sums_b / w).astype(lmq_ref.dtype)         # [M, d]
    wend = (li + 1) * w                                 # [M, 1]
    qdone_b = act & (wend > t0) & (wend <= new_end)
    lm_q_s = jnp.where(qdone_b, q_lm_b, lmq_ref[0, 0])

    ends_b = jnp.where(li < m_train, (li + 1) * w_a, wend)
    s_b = _dot(lm_q_s.astype(jnp.float32), k_ctx) / math.sqrt(d)
    s_b = jnp.where(cid < ends_b, s_b, NEG_INF)
    top_vals, top_loc = _topk(s_b, k_width)             # [M, K]
    new_valid = (top_vals > NEG_INF / 2).astype(jnp.int32)
    new_rows = _ctx_to_rows(top_loc, pt_ref, s, m_slot, w)
    p_b = _softmax(s_b)
    v_lm_b = _mm(p_b, v_ctx).astype(lmv_ref.dtype)
    scommit = act & (ends_b > t0) & (ends_b <= new_end)
    lm_v_s = jnp.where(scommit, v_lm_b, lmv_ref[0, 0])
    ei_s = jnp.where(scommit, new_rows, ei_ref[0, 0])
    ev_s = jnp.where(scommit, new_valid, ev_ref[0, 0])

    m_new = new_end // w
    q_sum_s = jnp.sum(jnp.where(li == m_new, sums_b, 0.0), axis=0,
                      keepdims=True)
    q_sum_s = jnp.where(act, q_sum_s, qs_ref[0, 0])

    # ---- 4. A system: the training head's n//m-sized prompt windows ----
    is_tr_n = (t0 + nid) < ntr                          # [M, nc]
    win_a = (t0 + nid) // w_a
    tok_a = (valid_n & is_tr_n & (win_a == lid)).astype(jnp.float32)
    sums_a = _mm(tok_a, ql)
    m0_a = t0 // w_a
    resume_a = (li == m0_a) & (t0 % w_a != 0) & (t0 < ntr)
    sums_a = sums_a + jnp.where(resume_a, pqs_ref[0, 0], 0.0)
    q_lm_a = (sums_a / w_a.astype(jnp.float32)).astype(plmq_ref.dtype)
    ends_a = (li + 1) * w_a                             # [M, 1]
    qdone_a = (act & (ends_a > t0) & (ends_a <= new_end) & (li < m_a))
    pre_lm_q_s = jnp.where(qdone_a, q_lm_a, plmq_ref[0, 0])

    # open-window sum: the resume contribution already sits inside
    # sums_a's open row, so selecting that row reproduces tail + resume
    open_a = new_end // w_a
    pre_q_sum_s = jnp.sum(jnp.where(li == open_a, sums_a, 0.0), axis=0,
                          keepdims=True)
    pre_q_sum_s = jnp.where(act, pre_q_sum_s, pqs_ref[0, 0])

    s_a = _dot(pre_lm_q_s.astype(jnp.float32), k_ctx) / math.sqrt(d)
    s_a = jnp.where((cid < ends_a) & (li < m_a), s_a, NEG_INF)
    tv_a, tl_a = _topk(s_a, k_width)                    # [M, K]
    v_lm_a = _mm(_softmax(s_a), v_ctx)                  # [M, d] f32

    # expert membership per landmark over context positions: A experts
    # are top-k positions; B experts are stored GLOBAL pool rows mapped
    # back through the page table (rows on no page of the slot hold
    # nothing — such entries are expert_valid-masked in the oracle)
    mem_a[...] = _membership(tl_a, tv_a > NEG_INF / 2, ctx)
    mem_b[...] = _membership(_rows_to_ctx(ei_s, pt_ref, s, m_slot, w),
                             ev_s == 1, ctx)

    # ---- 5. chunk attention, one tile of q_block windows at a time ----
    lmq_a = pre_lm_q_s.astype(jnp.float32)
    lmq_b = lm_q_s.astype(jnp.float32)
    lmv_b = lm_v_s.astype(jnp.float32)
    tq = q_block * w
    n_pt = nc // tq
    scale = 1.0 / math.sqrt(d)

    def tile(ti, _):
        gi = ti // n_pt
        p0 = pl.multiple_of((ti % n_pt) * tq, tq)
        qt = q_ref[0, 0, gi, pl.ds(p0, tq), :].astype(jnp.float32)
        pos = t0 + p0 + jax.lax.broadcasted_iota(jnp.int32, (tq, 1), 0)
        tr = pos < ntr                                  # A-system rows
        lm_i = jax.lax.broadcasted_iota(jnp.int32, (tq, m_slot), 1)

        # shared landmarks (doubling as routing logits), A/B per row
        avail_a = ((lm_i + 1) * w_a <= pos + 1) & (lm_i < m_a)
        avail_b = (lm_i + 1) * w <= pos + (0 if external else 1)
        sh_ok = (tr & avail_a) | (~tr & avail_b)
        r = jnp.where(tr, _dot(qt, lmq_a), _dot(qt, lmq_b)) * scale
        r = jnp.where(sh_ok, r, NEG_INF)

        # routed experts: top-s landmarks per row -> per-lane key counts
        oh = jnp.zeros((tq, m_slot), jnp.float32)
        r_route = r
        for _ in range(n_route):
            mx, ix = _first_argmax(r_route)
            hit = lm_i == ix
            oh = oh + jnp.where(hit & (mx > NEG_INF / 2), 1.0, 0.0)
            r_route = jnp.where(hit, -jnp.inf, r_route)
        wts = jnp.where(tr, _mm(oh, mem_a[...]), _mm(oh, mem_b[...]))

        # local window: [window start, pos] in context coordinates
        cpos = jax.lax.broadcasted_iota(jnp.int32, (tq, ctx), 1)
        win = jnp.where(tr, (pos // w_a) * w_a, (pos // w) * w)
        wts = wts + jnp.where((cpos >= win) & (cpos <= pos), 1.0, 0.0)

        # one softmax over shared landmarks + weighted context lanes
        sc = _dot(qt, k_ctx) * scale                    # [tq, ctx]
        ctx_ok = wts > 0.0
        m_sh = jnp.max(r, axis=-1, keepdims=True)
        m_cx = jnp.max(jnp.where(ctx_ok, sc, NEG_INF), axis=-1,
                       keepdims=True)
        mm = jnp.maximum(m_sh, m_cx)
        safe = jnp.where(mm == NEG_INF, 0.0, mm)
        p_sh = jnp.where(sh_ok, jnp.exp(r - safe), 0.0)
        p_cx = jnp.where(ctx_ok, wts * jnp.exp(sc - safe), 0.0)
        den = (jnp.sum(p_sh, axis=-1, keepdims=True)
               + jnp.sum(p_cx, axis=-1, keepdims=True))
        num = (jnp.where(tr, _mm(p_sh, v_lm_a), _mm(p_sh, lmv_b))
               + _mm(p_cx, v_ctx))
        out = jnp.where(den == 0.0, 0.0,
                        num / jnp.where(den == 0.0, 1.0, den))
        out = jnp.where(act, out, 0.0)
        o_ref[0, 0, gi, pl.ds(p0, tq), :] = out.astype(o_ref.dtype)
        return 0

    jax.lax.fori_loop(0, g * n_pt, tile, 0)

    # ---- 6. write back ----
    lmq_o[0, 0] = lm_q_s
    lmv_o[0, 0] = lm_v_s
    ei_o[0, 0] = ei_s
    ev_o[0, 0] = ev_s
    qs_o[0, 0] = q_sum_s
    plmq_o[0, 0] = pre_lm_q_s
    pqs_o[0, 0] = pre_q_sum_s


@functools.partial(
    jax.jit,
    static_argnames=("window", "k_width", "n_route", "external_finalize",
                     "q_block", "vmem_limit", "interpret"))
def mita_chunk_prefill_fused(q, k, v, lm_q, lm_v, expert_idx, expert_valid,
                             q_sum, pre_lm_q, pre_q_sum, k_pool, v_pool,
                             page_table, t0, n_valid, n_train, active,
                             window: int, k_width: int, n_route: int = 1,
                             external_finalize: bool = True,
                             q_block: int = 1, vmem_limit: int = 0,
                             interpret: bool = False):
    """Fused batched chunk prefill (+ in-place KV append).

    q: [S, Hkv, G, nc, d]; k/v: [S, Hkv, nc, d]; lm_q/lm_v/pre_lm_q:
    [S, Hkv, M, d]; expert_idx: [S, Hkv, M, K] GLOBAL pool rows;
    expert_valid: [S, Hkv, M, K] bool; q_sum/pre_q_sum: [S, Hkv, d] f32;
    k_pool/v_pool: [R + 1, Hkv, L] 32-bit, ``L >= d`` lanes per head row
    (`kernels.ops.pool_lanes`; row R is the scratch row);
    page_table: [S, M] i32; t0/n_valid/n_train: [S] i32; active: [S] bool.

    ``q_block`` (windows per attention tile, from
    `kernels.ops.select_prefill_q_block`) requires ``nc % window == 0``
    and ``q_block | (nc // window)``; every tile size gives the same
    state bit-for-bit.  ``vmem_limit`` (bytes, 0 = Mosaic's default) is
    the kernel's scoped-VMEM limit.

    Returns (out, lm_q, lm_v, expert_idx, expert_valid [i32], q_sum,
    pre_lm_q, pre_q_sum, k_pool, v_pool) — the pools aliased in/out, every
    other state tensor merged (inactive rows pass through bit-exactly).
    See `core.mita_decode.mita_batched_chunk_prefill` for the semantics
    this kernel must (and is pinned to) reproduce.
    """
    n_slots, hkv, g, nc, d = q.shape
    m_slot, kw = expert_idx.shape[-2:]
    assert kw == k_width
    assert q_block > 0 and nc % (q_block * window) == 0, \
        (nc, window, q_block)
    pdt = k_pool.dtype
    if pdt.itemsize != 4:
        raise ValueError(f"chunk-prefill kernel needs 32-bit pools, got "
                         f"{pdt}")
    ctx = m_slot * window
    lanes = k_pool.shape[-1]
    pad = ((0, 0), (0, 0), (0, 0), (0, lanes - d))

    def blk(*shape):
        return pl.BlockSpec((1, 1) + shape,
                            lambda s, h, *_: (s, h) + (0,) * len(shape))

    state_specs = [blk(m_slot, d), blk(m_slot, d), blk(m_slot, kw),
                   blk(m_slot, kw), blk(1, d), blk(m_slot, d), blk(1, d)]
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(n_slots, hkv),
        in_specs=[blk(g, nc, d), any_spec, any_spec, *state_specs,
                  any_spec, any_spec],     # chunk k/v and pools in HBM
        out_specs=[blk(g, nc, d), *state_specs, any_spec, any_spec],
        scratch_shapes=[
            pltpu.VMEM((ctx, lanes), pdt),
            pltpu.VMEM((ctx, lanes), pdt),
            pltpu.VMEM((m_slot, ctx), jnp.float32),
            pltpu.VMEM((m_slot, ctx), jnp.float32),
            pltpu.SemaphoreType.DMA(()),
        ],
    )
    kern = functools.partial(_chunk_kernel, window=window, k_width=k_width,
                             n_route=n_route, external=external_finalize,
                             q_block=q_block)
    outs = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((n_slots, hkv, g, nc, d), q.dtype),
            jax.ShapeDtypeStruct(lm_q.shape, lm_q.dtype),
            jax.ShapeDtypeStruct(lm_v.shape, lm_v.dtype),
            jax.ShapeDtypeStruct(expert_idx.shape, jnp.int32),
            jax.ShapeDtypeStruct(expert_valid.shape, jnp.int32),
            jax.ShapeDtypeStruct((n_slots, hkv, 1, d), jnp.float32),
            jax.ShapeDtypeStruct(pre_lm_q.shape, pre_lm_q.dtype),
            jax.ShapeDtypeStruct((n_slots, hkv, 1, d), jnp.float32),
            jax.ShapeDtypeStruct(k_pool.shape, pdt),
            jax.ShapeDtypeStruct(v_pool.shape, pdt),
        ],
        # operand indices count the 5 scalar-prefetch args
        input_output_aliases={15: 8, 16: 9},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit or None),
        interpret=interpret,
        name="mita_chunk_prefill_fused",
    )(page_table.astype(jnp.int32), t0.astype(jnp.int32),
      n_valid.astype(jnp.int32), n_train.astype(jnp.int32),
      active.astype(jnp.int32),
      q, jnp.pad(k.astype(pdt), pad), jnp.pad(v.astype(pdt), pad),
      lm_q, lm_v,
      expert_idx.astype(jnp.int32), expert_valid.astype(jnp.int32),
      q_sum[:, :, None], pre_lm_q, pre_q_sum[:, :, None], k_pool, v_pool)
    out, lmq, lmv, ei, ev, qs, plmq, pqs, kp, vp = outs
    return out, lmq, lmv, ei, ev, qs[:, :, 0], plmq, pqs[:, :, 0], kp, vp
