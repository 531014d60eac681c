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
dispatch): q/out `2·G·d`, landmark tiles `2·M·d`, local page `2·w·d`, two
expert KV tile buffers `4·K·d`, plus the `M·K` expert bias table; in SMEM
the `s·G` tiles' expert ordinals and two experts' row ids `2·K`.

Issue order.  The walk is bound by DMA latency, not bytes (one 512-byte
row per copy), so it keeps as many copies in flight as it can:

  1. the new row's reads, the fused append and the local page's K and V
     copies start together, then are awaited together;
  2. routing runs for all ``s`` passes first: the walk is one flat
     sequence of ``s·G`` expert tiles (route j of query head gi is tile
     ``j·G + gi``), a tile with no walk (inactive slot, or no visible
     landmark to route to) marked in SMEM and skipped under `pl.when`;
  3. tile i+1's row ids are already in SMEM when tile i is awaited: every
     one of its K rows' K and V copies starts into the other tile buffer
     (``ROW_ISSUE_UNROLL`` rows per issue-loop iteration), then tile i+2's
     ids start, then tile i is awaited — one wait per buffer and pool for
     all its rows' bytes — and its `q·Kᵀ`, softmax and `p·V` run while
     tile i+1 lands.

The routed partials merge per route in the same order as the serial walk,
so outputs do not depend on the issue order; ``REPRO_DMA_PIPELINE=0``
walks each tile serially, one row's copy at a time, for debugging
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
# routed-row copies started per iteration of a tile's issue loop (Pallas
# TPU unrolls a loop only whole, so this is done by hand)
ROW_ISSUE_UNROLL = 8


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

    # the new row HBM->VMEM (register patch below), the fused append
    # HBM->HBM straight into its pool row, and the local page HBM->VMEM in
    # token order, all in flight together; the appended position is patched
    # from registers, so the result never depends on append/read ordering
    head = pl.ds(h, 1)
    copies = [pltpu.make_async_copy(kn_hbm.at[s, head], knew, sem),
              pltpu.make_async_copy(vn_hbm.at[s, head], vnew, sem),
              pltpu.make_async_copy(kpool_ref.at[pl.ds(page0, w), h], kloc,
                                    sem),
              pltpu.make_async_copy(vpool_ref.at[pl.ds(page0, w), h], vloc,
                                    sem)]
    if fuse_append:
        copies += [pltpu.make_async_copy(src.at[s, head],
                                         dst.at[row_new, head], sem)
                   for src, dst in ((kn_hbm, kpout_ref),
                                    (vn_hbm, vpout_ref))]
    for cp in copies:
        cp.start()
    for cp in copies:
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
    k_width = ketile.shape[1]

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
    # must be scalars: each tile's expert ordinal goes vector -> SMEM
    # through a full reduction (-1: no walk, for an inactive slot or a
    # route with no visible landmark, whose output is masked anyway), and
    # the expert's K row ids are DMA'd HBM -> SMEM.  Tile i = j·G + gi is
    # route j of query head gi.
    g_ids = jax.lax.broadcasted_iota(jnp.int32, (g, 1), 0)
    r_route = r
    oks = []
    for j in range(n_route):
        mx = jnp.max(r_route, axis=-1, keepdims=True)        # [G, 1]
        e_j = jnp.min(jnp.where(r_route == mx, lm_ids, m_slot), axis=-1,
                      keepdims=True)                         # [G, 1]
        ok_j = mx > NEG_INF / 2
        r_route = jnp.where(lm_ids == e_j, NEG_INF, r_route)
        e_live = jnp.where(ok_j, e_j, -1)
        for gi in range(g):
            e_sm[j * g + gi] = jnp.where(
                act, jnp.sum(jnp.where(g_ids == gi, e_live, 0)), -1)
        oks.append(ok_j)

    n_tiles = n_route * g

    def live(i):
        return e_sm[i] >= 0

    def expert(i):
        return jnp.minimum(jnp.maximum(e_sm[i], 0), m_slot - 1)

    def ids_copy(i):
        # one expert's row ids HBM -> SMEM; at most one is in flight
        return pltpu.make_async_copy(ei_hbm.at[s, h, expert(i)],
                                     rows_sm.at[i % 2], sem)

    def row_copies(i, kk):
        b = i % 2
        row = rows_sm[b, kk]
        return (pltpu.make_async_copy(kpool_ref.at[pl.ds(row, 1), h],
                                      ketile.at[b, pl.ds(kk, 1)],
                                      psem.at[b, 0]),
                pltpu.make_async_copy(vpool_ref.at[pl.ds(row, 1), h],
                                      vetile.at[b, pl.ds(kk, 1)],
                                      psem.at[b, 1]))

    def start_rows(i):
        # every row of the tile in flight before any is awaited
        unroll = math.gcd(k_width, ROW_ISSUE_UNROLL)

        def issue(kb, carry):
            for u in range(unroll):
                for cp in row_copies(i, kb * unroll + u):
                    cp.start()
            return carry

        jax.lax.fori_loop(0, k_width // unroll, issue, 0)

    def wait_rows(i):
        # one wait per tile buffer and pool, for all its rows' bytes
        b = i % 2
        for pool, tile, c in ((kpool_ref, ketile, 0), (vpool_ref, vetile, 1)):
            pltpu.make_async_copy(pool.at[pl.ds(0, k_width), h], tile.at[b],
                                  psem.at[b, c]).wait()

    def serial_walk(i):
        cp = ids_copy(i)
        cp.start()
        cp.wait()

        def walk(kk, carry):
            for cp in row_copies(i, kk):
                cp.start()
                cp.wait()
            return carry

        jax.lax.fori_loop(0, k_width, walk, 0)

    if pipeline:
        # tile 0's ids and rows, and tile 1's ids, ahead of the loop
        @pl.when(live(0))
        def _():
            cp = ids_copy(0)
            cp.start()
            cp.wait()
            start_rows(0)

        if n_tiles > 1:
            @pl.when(live(1))
            def _():
                ids_copy(1).start()

    m_rows, l_rows, o_rows = [], [], []
    for i in range(n_tiles):
        j, gi = divmod(i, g)
        if pipeline:
            # tile i+1's rows into the other buffer, and tile i+2's ids,
            # go in flight before tile i is awaited and computed
            if i + 1 < n_tiles:
                @pl.when(live(i + 1))
                def _():
                    ids_copy(i + 1).wait()
                    start_rows(i + 1)

            if i + 2 < n_tiles:
                @pl.when(live(i + 2))
                def _():
                    ids_copy(i + 2).start()

            pl.when(live(i))(lambda: wait_rows(i))
        else:
            pl.when(live(i))(lambda: serial_walk(i))

        b = i % 2
        bias = eb_ref[0, 0, pl.ds(expert(i), 1), :]         # [1, K]
        s_e = jax.lax.dot_general(
            q[gi:gi + 1], ketile[b].astype(jnp.float32)[:, :d],
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)              # [1, K]
        s_e = s_e + bias
        s_e = jnp.where(oks[j][gi:gi + 1], s_e, NEG_INF)
        m_e, l_e, p_e = _partial(s_e)
        o_e = jax.lax.dot_general(p_e, vetile[b].astype(jnp.float32)[:, :d],
                                  (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        # a tile that was not walked holds stale rows, maybe not finite
        o_e = jnp.where(live(i), o_e, 0.0)
        m_rows.append(m_e)
        l_rows.append(l_e)
        o_rows.append(o_e)
        if gi == g - 1:
            m_acc, l_acc, o_acc = _merge(
                m_acc, l_acc, o_acc, jnp.concatenate(m_rows),
                jnp.concatenate(l_rows), jnp.concatenate(o_rows))
            m_rows, l_rows, o_rows = [], [], []

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
            pltpu.VMEM((2, k_width, lanes), k_pool.dtype),
            pltpu.VMEM((2, k_width, lanes), v_pool.dtype),
            pltpu.SMEM((n_route * g,), jnp.int32),  # each tile's expert
            pltpu.SMEM((2, k_width), jnp.int32),    # two experts' row ids
            pltpu.SemaphoreType.DMA(()),
            pltpu.SemaphoreType.DMA((2, 2)),   # (tile buffer, K/V)
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
