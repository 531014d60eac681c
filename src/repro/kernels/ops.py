"""Dispatch wrappers: Pallas kernels on TPU, interpret/XLA fallbacks on CPU.

`routed_expert_partial` is the integration point used by
`repro.core.mita_sparse` when ``impl="pallas"``: it takes the sorted
sub-queries + expert bank and returns online-softmax partials compatible
with `repro.core.combine.Partial`.

`paged_decode_attend` is the integration point used by
`repro.core.mita_decode.mita_paged_decode_step`: the fused paged-decode
kernel (`kernels.mita_paged_attn`) walks page tables in VMEM and gathers
routed-expert rows by global row id; the XLA gather path in
`core.mita_decode` stays as the oracle and the fallback whenever
`use_paged_kernel` says no.

Tunables (satellite of the module constants they replace):
  * ``REPRO_VMEM_BUDGET_BYTES`` — per-kernel VMEM working-set budget used
    by every fits/dispatch decision (default 32 MiB).  `DecodeConfig
    .vmem_budget` overrides it per decode config.  Each kernel is compiled
    with a scoped-VMEM limit derived from its own estimate
    (`kernel_vmem_limit`), so a working set that fits the budget also
    compiles.
  * ``REPRO_BLOCK_Q`` / ``REPRO_BLOCK_K`` — default kernel block sizes for
    the flash / expert kernels when the caller passes none.
"""

from __future__ import annotations

import contextlib
import math
import os
import warnings
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import flash_attn as _fa
from repro.kernels import mita_chunk_prefill as _mcp
from repro.kernels import mita_expert_attn as _mea
from repro.kernels import mita_paged_attn as _mpa
from repro.kernels import mita_paged_finalize as _mpf

# room for the largest serving kernel — the chunk prefill at a ~4k-token
# context, ~20 MiB by its estimate — which compiles for v5e at that limit
# (tests/test_tpu_compile.py)
DEFAULT_VMEM_BUDGET_BYTES = 32 * 2**20
DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128


def vmem_budget_bytes() -> int:
    """Effective VMEM working-set budget: env override or the default."""
    return int(os.environ.get("REPRO_VMEM_BUDGET_BYTES",
                              DEFAULT_VMEM_BUDGET_BYTES))


def default_block_q() -> int:
    return int(os.environ.get("REPRO_BLOCK_Q", DEFAULT_BLOCK_Q))


def default_block_k() -> int:
    return int(os.environ.get("REPRO_BLOCK_K", DEFAULT_BLOCK_K))


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def kernel_interpret() -> bool:
    """Whether Pallas kernels run in interpret mode: compiled on the TPU,
    interpreted on the CPU backend (tests), and refused anywhere else — a
    kernel never silently interprets on an accelerator it was not built
    for."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(f"Pallas TPU kernels cannot run on the {backend!r} "
                       "backend (compiled on TPU, interpreted on CPU only)")


def kernel_vmem_limit(need: int) -> int:
    """Scoped-VMEM limit a kernel is compiled with, from its working-set
    estimate: 25% + 2 MiB of headroom for Mosaic's own temporaries (the
    estimators are calibrated against the compiler in
    tests/test_tpu_compile.py)."""
    return need + need // 4 + 2 * 2**20


LANES = 128


def pool_lanes(head_dim: int) -> int:
    """Width of one KV head's row in the paged pools: the head dim, rounded
    up to whole 128-lane tiles on the TPU.  Mosaic DMAs one head's row of
    a ``[R, Hkv, lanes]`` pool only as whole lane tiles, and XLA already
    pads a narrower row to a full tile there, so the rounding costs no
    HBM.  Interpret mode (CPU) keeps the head dim."""
    return -(-head_dim // LANES) * LANES if on_tpu() else head_dim


def flash_attention(q, k, v, causal: bool = False,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None):
    """[B,H,N,d] flash attention; interpret mode on CPU."""
    if interpret is None:
        interpret = kernel_interpret()
    return _fa.flash_attention(q, k, v, causal=causal,
                               block_q=block_q or default_block_q(),
                               block_k=block_k or default_block_k(),
                               interpret=interpret)


def expert_bank_fits(m: int, k: int, d: int, bytes_per_el: int = 2,
                     budget: int = 0) -> bool:
    return 2 * m * k * d * bytes_per_el <= (budget or vmem_budget_bytes())


# -------------------------------------------------- paged-cache indirection --
#
# The serving engine (repro.serve) keeps one KV pool per layer shared by all
# requests; a request owns a set of fixed-size, window-aligned pages named by
# a page table.  Every decode-time gather then goes through row indirection
# instead of slicing a per-request [B, Hkv, C, d] cache.  These wrappers are
# the dispatch point: the fused Pallas kernel (`paged_decode_attend`) covers
# the decode hot path; the XLA gathers remain for the finalize / chunk-
# prefill paths and as the decode fallback/oracle.

def gather_pool_rows(pool: jax.Array, rows: jax.Array,
                     d: int) -> jax.Array:
    """Gather per-(slot, kv-head) rows from a shared KV pool.

    pool: [R, Hkv, lanes] — flattened page pool (row = page_id *
    page_size + off; ``lanes >= d``, see `pool_lanes`).  rows: [S, Hkv, n]
    int32 global row ids (may repeat; must be in-bounds).  Returns
    [S, Hkv, n, d].
    """
    pool_t = jnp.swapaxes(pool, 0, 1)                  # [Hkv, R, lanes]
    return jnp.take_along_axis(pool_t[None], rows[..., None],
                               axis=2)[..., :d]


def gather_pages(pool: jax.Array, page_ids: jax.Array,
                 page_size: int, d: int,
                 owned: Optional[jax.Array] = None) -> jax.Array:
    """Gather whole pages in page-table order (sequential token order).

    pool: [R, Hkv, lanes] (``lanes >= d``, see `pool_lanes`);
    page_ids: [S, P] int32.  Returns [S, P * page_size, Hkv, d].

    ``owned`` (optional [S] int32): pages each slot actually owns
    (``ceil(t / page_size)``).  Table entries at ordinal >= owned are
    redirected to the pool's trailing scratch row instead of gathering
    whatever page the unused table entry happens to name — unused entries
    are in-bounds but unowned (scheduler invariant 4), so without the
    redirect a short request copies other requests' pages only to mask
    them downstream.
    """
    rows = page_ids[..., None] * page_size + jnp.arange(page_size)
    if owned is not None:
        scratch = pool.shape[0] - 1
        is_owned = (jnp.arange(page_ids.shape[-1])[None, :, None]
                    < owned[:, None, None])
        rows = jnp.where(is_owned, rows, scratch)
    return pool[rows.reshape(rows.shape[:-2] + (-1,))][..., :d]


def scatter_pool_rows(pool: jax.Array, rows: jax.Array,
                      new: jax.Array) -> jax.Array:
    """Write rows into the pool in its row layout (`pool_lanes`).

    pool: [R, Hkv, lanes]; rows: [...] int32 global row ids (scratch-row
    duplicates allowed for inactive slots / padded tokens); new:
    [..., Hkv, d].  Returns the updated pool; lanes past ``d`` are zero.
    """
    new = new.astype(pool.dtype)
    pad = pool.shape[-1] - new.shape[-1]
    if pad:
        new = jnp.pad(new, [(0, 0)] * (new.ndim - 1) + [(0, pad)])
    return pool.at[rows].set(new)


# ------------------------------------------------- fused paged-decode attn --

def paged_attention_vmem_bytes(window: int, m: int, k_width: int, g: int,
                               d: int, itemsize: int = 4) -> int:
    """Per-program VMEM working set of the fused paged-decode kernel:
    q + out, the landmark tiles, the local page, two expert KV tiles (one
    computed while the next is gathered), and the expert index/bias
    tables (`kernels.mita_paged_attn` docstring)."""
    tiles = (2 * g * d          # q + out
             + 2 * m * d        # lm_q + lm_v
             + 2 * window * d   # local page (k, v)
             + 4 * k_width * d)  # two expert KV tile buffers
    tables = m * k_width * (4 + 4)   # expert_idx (i32) + bias (f32)
    return tiles * itemsize + tables


# Paged-decode analogue of `_PREFILL_KERNEL_FALLBACKS` below: a dispatch
# decision that WANTED the fused paged-decode kernel but fell back to XLA
# because the working set exceeded the VMEM budget.  Counted at trace time
# (one decision per compiled shape).  Surfaced as
# ``stats()["paged_kernel_fallbacks"]`` by the MiTA serving backend.
_PAGED_KERNEL_FALLBACKS = 0
_PAGED_FALLBACK_WARNED = False


def paged_kernel_fallbacks() -> int:
    """Process-wide count of paged-decode kernel→XLA VMEM fallbacks."""
    return _PAGED_KERNEL_FALLBACKS


def use_paged_kernel(impl: str, *, window: int, m: int, k_width: int,
                     g: int, d: int, itemsize: int = 4,
                     budget: int = 0) -> bool:
    """Decode-step dispatch: fused Pallas kernel vs the XLA gather oracle.

    ``impl``: "auto" (kernel on TPU when the working set fits the VMEM
    budget), "kernel" (force, still bounded by the budget so an oversized
    config degrades to the fallback instead of failing to lower), or "xla".
    ``budget`` = 0 uses `vmem_budget_bytes()` (env-overridable).

    A "no" that is due to the VMEM budget, rather than impl="xla" or
    running off-TPU in auto mode, increments `paged_kernel_fallbacks` and
    warns once per process, mirroring the chunk-prefill dispatch.
    """
    global _PAGED_KERNEL_FALLBACKS, _PAGED_FALLBACK_WARNED
    if impl == "xla":
        return False
    if impl not in ("auto", "kernel"):
        raise ValueError(f"unknown paged impl {impl!r}")
    need = paged_attention_vmem_bytes(window, m, k_width, g, d, itemsize)
    have = budget or vmem_budget_bytes()
    fits = need <= have
    if not fits and (impl == "kernel" or on_tpu()):
        _PAGED_KERNEL_FALLBACKS += 1
        if not _PAGED_FALLBACK_WARNED:
            _PAGED_FALLBACK_WARNED = True
            warnings.warn(
                f"paged-decode kernel working set {need} B exceeds the "
                f"VMEM budget {have} B (m={m}, window={window}, d={d}); "
                "dispatching to the XLA path — raise "
                "REPRO_VMEM_BUDGET_BYTES / DecodeConfig.vmem_budget to "
                "keep the fused kernel "
                "(further fallbacks are counted, not warned)",
                RuntimeWarning, stacklevel=2)
    if impl == "kernel":
        return fits
    return on_tpu() and fits


def paged_decode_attend(q, k_new, v_new, lm_q, lm_v, expert_idx,
                        expert_valid, k_pool, v_pool, page_table, t, active,
                        m_cnt, *, window: int, n_route: int,
                        fuse_append: bool,
                        interpret: Optional[bool] = None):
    """Kernel-backed fused decode step: append + three-branch attend.

    See `kernels.mita_paged_attn.mita_paged_attention` for the contract.
    Returns (out [S, Hkv, G, d], k_pool, v_pool) with the pools aliased
    in/out (new row written in place when ``fuse_append``).
    """
    if interpret is None:
        interpret = kernel_interpret()
    return _mpa.mita_paged_attention(
        q, k_new, v_new, lm_q, lm_v, expert_idx, expert_valid,
        k_pool, v_pool, page_table, t, active, m_cnt,
        window=window, n_route=n_route, fuse_append=fuse_append,
        pipeline=dma_pipeline(), interpret=interpret,
        vmem_limit=0 if interpret else kernel_vmem_limit(
            paged_attention_vmem_bytes(
                window, lm_q.shape[-2], expert_idx.shape[-1], q.shape[2],
                k_pool.shape[-1], k_pool.dtype.itemsize)))


def dma_pipeline() -> bool:
    """REPRO_DMA_PIPELINE: the paged-decode kernel's routed-expert walk
    keeps every row of an expert tile in flight, and gathers the next
    tile into a second buffer while one is computed (issue order in the
    `kernels.mita_paged_attn` docstring).  Default on; set to 0 to walk
    each tile one row's copy at a time (debug / parity bisect)."""
    return os.environ.get("REPRO_DMA_PIPELINE", "1") != "0"


# ------------------------------------------------ fused chunk-prefill attn --

def chunk_prefill_vmem_bytes(nc: int, window: int, m: int, k_width: int,
                             g: int, d: int, itemsize: int = 4,
                             q_block: int = 1) -> int:
    """Per-program VMEM working set of the fused chunk-prefill kernel
    (`kernels.mita_chunk_prefill`): the gathered context in the pool dtype
    plus its f32 working copy, the two expert-membership tables, the
    landmark-build score rows, the double-buffered chunk q/out blocks, and
    the ``[q_block·w, ctx]`` f32 temporaries of one attention tile.  An
    upper bound on what Mosaic allocates (calibrated against the compiler
    at qwen3-0.6b widths in tests/test_tpu_compile.py)."""
    ctx = m * window
    tq = q_block * window
    context = 2 * ctx * d * (itemsize + 4)
    membership = 2 * m * ctx * 4
    landmarks = (6 * m * ctx + 2 * m * nc + 8 * m * d
                 + 4 * m * k_width) * 4
    blocks = 4 * g * nc * d * 4
    tile = 3 * tq * ctx * 4
    return context + membership + landmarks + blocks + tile


def select_prefill_q_block(nc: int, window: int, m: int, k_width: int,
                           g: int, d: int, itemsize: int = 4,
                           budget: int = 0) -> Optional[int]:
    """Pick the attention tile of the chunk-prefill kernel.

    Returns the largest ``q_block`` (windows per tile, a divisor of
    ``nc // window``) whose working set fits the VMEM budget, or None when
    none does or the chunk is not window-aligned (caller falls back to
    XLA).  Larger tiles mean fewer tile iterations; q_block = 1 is the
    floor the budget can force.
    """
    have = budget or vmem_budget_bytes()
    if nc % window or nc < window:
        return None
    nw = nc // window
    for qb in range(nw, 0, -1):
        if nw % qb:
            continue
        if chunk_prefill_vmem_bytes(nc, window, m, k_width, g, d,
                                    itemsize, q_block=qb) <= have:
            return qb
    return None


# A dispatch decision that WANTED the fused chunk-prefill kernel but fell
# back to XLA because the working set exceeded the VMEM budget.  Counted at
# trace time (one decision per compiled shape, not per dispatch) — at
# production G·nc·ctx shapes the fallback used to be silent, so an engine
# could run an order of magnitude slower with no signal.  The serving
# engine surfaces the count as ``stats()["prefill_kernel_fallbacks"]``.
_PREFILL_KERNEL_FALLBACKS = 0
_PREFILL_FALLBACK_WARNED = False


def prefill_kernel_fallbacks() -> int:
    """Process-wide count of chunk-prefill kernel→XLA VMEM fallbacks."""
    return _PREFILL_KERNEL_FALLBACKS


def use_prefill_kernel(impl: str, *, nc: int, window: int, m: int,
                       k_width: int, g: int, d: int, itemsize: int = 4,
                       budget: int = 0) -> bool:
    """Chunk-prefill dispatch: fused Pallas kernel vs the XLA gather oracle.

    Same tri-state as `use_paged_kernel` (``DecodeConfig.prefill_impl``),
    with a process-wide override via ``REPRO_PREFILL_IMPL`` — the serving
    engine never retraces on an impl flip because the choice is made at
    trace time.

    A "no" that is due to the VMEM budget (or a chunk that is not
    window-aligned), rather than impl="xla" or running off-TPU in auto
    mode, increments `prefill_kernel_fallbacks` and warns once per process
    — production shapes that silently degrade to the XLA path are an
    observability bug, not a preference.
    """
    global _PREFILL_KERNEL_FALLBACKS, _PREFILL_FALLBACK_WARNED
    impl = os.environ.get("REPRO_PREFILL_IMPL", impl)
    if impl == "xla":
        return False
    if impl not in ("auto", "kernel"):
        raise ValueError(f"unknown prefill impl {impl!r}")
    q_block = select_prefill_q_block(nc, window, m, k_width, g, d,
                                     itemsize, budget)
    fits = q_block is not None
    if not fits and (impl == "kernel" or on_tpu()):
        _PREFILL_KERNEL_FALLBACKS += 1
        if not _PREFILL_FALLBACK_WARNED:
            _PREFILL_FALLBACK_WARNED = True
            need = chunk_prefill_vmem_bytes(nc, window, m, k_width, g, d,
                                            itemsize, q_block=1)
            have = budget or vmem_budget_bytes()
            warnings.warn(
                f"chunk-prefill kernel working set {need} B at the "
                f"smallest tile exceeds the VMEM budget {have} B "
                f"(nc={nc}, window={window}, m={m}, k_width={k_width}, "
                f"g={g}, d={d}, itemsize={itemsize}; the kernel also "
                "needs a window-aligned chunk); dispatching to the "
                "XLA path — raise REPRO_VMEM_BUDGET_BYTES / "
                "DecodeConfig.vmem_budget or shrink the chunk to keep "
                "the fused kernel "
                "(further fallbacks are counted, not warned)",
                RuntimeWarning, stacklevel=2)
    if impl == "kernel":
        return fits
    return on_tpu() and fits


def batched_chunk_prefill(q, k, v, lm_q, lm_v, expert_idx, expert_valid,
                          q_sum, pre_lm_q, pre_q_sum, k_pool, v_pool,
                          page_table, t0, n_valid, n_train, active, *,
                          window: int, k_width: int, n_route: int,
                          external_finalize: bool, q_block: int,
                          interpret: Optional[bool] = None):
    """Kernel-backed batched chunk prefill: append + landmark build +
    three-branch chunk attention for every active row in one kernel.

    Operates on COMPACT per-row slot state ([P, ...] — the caller gathers
    rows by slot id and scatters the returned updates back); the pools are
    aliased in/out.  ``q_block`` (windows per attention tile, from
    `select_prefill_q_block`) is static — a budget change retraces.  See
    `kernels.mita_chunk_prefill.mita_chunk_prefill_fused` for the full
    contract.
    """
    if interpret is None:
        interpret = kernel_interpret()
    return _mcp.mita_chunk_prefill_fused(
        q, k, v, lm_q, lm_v, expert_idx, expert_valid, q_sum, pre_lm_q,
        pre_q_sum, k_pool, v_pool, page_table, t0, n_valid, n_train,
        active, window=window, k_width=k_width, n_route=n_route,
        external_finalize=external_finalize, q_block=q_block,
        interpret=interpret,
        vmem_limit=0 if interpret else kernel_vmem_limit(
            chunk_prefill_vmem_bytes(
                q.shape[3], window, lm_q.shape[-2], k_width, q.shape[2],
                k_pool.shape[-1], k_pool.dtype.itemsize, q_block=q_block)))


# ------------------------------------------------ fused paged finalize ----

def paged_finalize_vmem_bytes(window: int, m: int, k_width: int, d: int,
                              itemsize: int = 4) -> int:
    """Per-program VMEM working set of the fused paged-finalize kernel:
    the gathered slot context in the pool dtype plus its f32 working
    copy, the double-buffered landmark / expert-table tiles in and out,
    and the f32 score, top-k and softmax rows (`kernels.
    mita_paged_finalize`; calibrated like `chunk_prefill_vmem_bytes`)."""
    ctx = m * window
    context = 2 * ctx * d * (itemsize + 4)
    tiles = 2 * (4 * m * d + 4 * m * k_width + 2 * d) * 4
    scores = 8 * ctx * 4
    return context + tiles + scores


# Finalize analogue of the two fallback counters above: a dispatch decision
# that WANTED the fused finalize kernel but fell back to the XLA gathers
# because the working set exceeded the VMEM budget.  Counted at trace time.
# Surfaced as ``stats()["finalize_kernel_fallbacks"]`` by the MiTA backend.
_FINALIZE_KERNEL_FALLBACKS = 0
_FINALIZE_FALLBACK_WARNED = False


def finalize_kernel_fallbacks() -> int:
    """Process-wide count of paged-finalize kernel→XLA VMEM fallbacks."""
    return _FINALIZE_KERNEL_FALLBACKS


def use_finalize_kernel(impl: str, *, window: int, m: int, k_width: int,
                        d: int, itemsize: int = 4, budget: int = 0) -> bool:
    """Paged-finalize dispatch: fused Pallas kernel vs the XLA gather
    oracle in `core.mita_decode._paged_finalize`.

    Same tri-state as `use_paged_kernel` (``DecodeConfig.finalize_impl``),
    with a process-wide override via ``REPRO_FINALIZE_IMPL``.  A "no" due
    to the VMEM budget (rather than impl="xla" or running off-TPU in auto
    mode) increments `finalize_kernel_fallbacks`
    and warns once.
    """
    global _FINALIZE_KERNEL_FALLBACKS, _FINALIZE_FALLBACK_WARNED
    impl = os.environ.get("REPRO_FINALIZE_IMPL", impl)
    if impl == "xla":
        return False
    if impl not in ("auto", "kernel"):
        raise ValueError(f"unknown finalize impl {impl!r}")
    need = paged_finalize_vmem_bytes(window, m, k_width, d, itemsize)
    have = budget or vmem_budget_bytes()
    fits = need <= have
    if not fits and (impl == "kernel" or on_tpu()):
        _FINALIZE_KERNEL_FALLBACKS += 1
        if not _FINALIZE_FALLBACK_WARNED:
            _FINALIZE_FALLBACK_WARNED = True
            warnings.warn(
                f"paged-finalize kernel working set {need} B exceeds the "
                f"VMEM budget {have} B (window={window}, m={m}, "
                f"k_width={k_width}, d={d}, itemsize={itemsize}); "
                "dispatching to the XLA path — raise "
                "REPRO_VMEM_BUDGET_BYTES / DecodeConfig.vmem_budget to "
                "keep the fused kernel "
                "(further fallbacks are counted, not warned)",
                RuntimeWarning, stacklevel=2)
    if impl == "kernel":
        return fits
    return on_tpu() and fits


def paged_finalize(q_sum, lm_q, lm_v, expert_idx, expert_valid, k_pool,
                   v_pool, page_table, t_new, due, *, window: int,
                   k_width: int, interpret: Optional[bool] = None):
    """Kernel-backed paged landmark finalize: pool the completed window's
    queries into a landmark row and rebuild the top-k expert gather, per
    (slot, KV-head) program, reading pages via DMA.

    Returns (lm_q, lm_v, expert_idx, expert_valid i32, q_sum) — the
    caller merges them into the paged state.  See
    `kernels.mita_paged_finalize.mita_paged_finalize_fused`.
    """
    if interpret is None:
        interpret = kernel_interpret()
    return _mpf.mita_paged_finalize_fused(
        q_sum, lm_q, lm_v, expert_idx, expert_valid, k_pool, v_pool,
        page_table, t_new, due, window=window, k_width=k_width,
        interpret=interpret,
        vmem_limit=0 if interpret else kernel_vmem_limit(
            paged_finalize_vmem_bytes(window, lm_q.shape[-2], k_width,
                                      k_pool.shape[-1],
                                      k_pool.dtype.itemsize)))


# ------------------------------------------------ fallback counter scope --

def fallback_counters() -> dict:
    """Snapshot of every kernel→XLA fallback counter (process-wide)."""
    return {"prefill": _PREFILL_KERNEL_FALLBACKS,
            "paged": _PAGED_KERNEL_FALLBACKS,
            "finalize": _FINALIZE_KERNEL_FALLBACKS}


def reset_fallback_counters() -> None:
    """Zero all fallback counters (and re-arm the warn-once flags) so a
    bench run or test reports only its own dispatch decisions."""
    global _PREFILL_KERNEL_FALLBACKS, _PREFILL_FALLBACK_WARNED
    global _PAGED_KERNEL_FALLBACKS, _PAGED_FALLBACK_WARNED
    global _FINALIZE_KERNEL_FALLBACKS, _FINALIZE_FALLBACK_WARNED
    _PREFILL_KERNEL_FALLBACKS = 0
    _PREFILL_FALLBACK_WARNED = False
    _PAGED_KERNEL_FALLBACKS = 0
    _PAGED_FALLBACK_WARNED = False
    _FINALIZE_KERNEL_FALLBACKS = 0
    _FINALIZE_FALLBACK_WARNED = False


@contextlib.contextmanager
def scoped_fallback_counters():
    """Scope the fallback counters to a block: yields a dict that is
    filled with this block's deltas on exit.  Counters keep accumulating
    globally (backends that hold base snapshots stay correct); only the
    yielded view is scoped.

        with ops.scoped_fallback_counters() as fb:
            run_bench()
        assert fb["prefill"] == 0
    """
    base = fallback_counters()
    delta: dict = {}
    try:
        yield delta
    finally:
        now = fallback_counters()
        for key, val in now.items():
            delta[key] = val - base[key]


def routed_expert_partial(q_sorted, assign, k_e, v_e, valid,
                          block_q: Optional[int] = None,
                          interpret: Optional[bool] = None):
    """Kernel-backed routed-expert partials with arbitrary lead dims.

    q_sorted: [..., NS, d]; assign: [..., NS];
    k_e/v_e: [kv_lead..., M, K, d] (lead may contain broadcast-1 dims);
    valid: [kv_lead..., M, K].
    Returns (o, m_stat, l) with q_sorted's lead dims.  NS need not divide
    the block size — `mita_expert_attention` pads internally.
    """
    if interpret is None:
        interpret = kernel_interpret()
    if block_q is None:
        block_q = default_block_q()
    lead = q_sorted.shape[:-2]
    ns, d = q_sorted.shape[-2:]
    m, kw = k_e.shape[-3], k_e.shape[-2]

    def bcast(x, trailing):
        tgt = lead + x.shape[-trailing:]
        return jnp.broadcast_to(x, tgt).reshape((1, -1) + x.shape[-trailing:])

    q4 = q_sorted.reshape((1, -1, ns, d))
    a4 = assign.reshape((1, -1, ns))
    ke4 = bcast(k_e, 3)
    ve4 = bcast(v_e, 3)
    va4 = bcast(valid, 2)
    o, ms, l = _mea.mita_expert_attention(
        q4, a4, ke4, ve4, va4,
        block_q=min(block_q, ns), interpret=interpret)
    return (o.reshape(lead + (ns, d)), ms.reshape(lead + (ns,)),
            l.reshape(lead + (ns,)))
