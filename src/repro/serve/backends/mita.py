"""Paged MiTA serving backend — the engine's original device-side path.

Everything the PR-1..4 engine knew about MiTA lives here now, behavior-
unchanged and pinned by the existing greedy-bit-parity tests: the paged
KV/landmark/expert pools (`core.mita_decode.PagedMiTAState`), the fused
whole-batch decode step (window-boundary landmark finalize behind a scalar
`lax.cond`, optional fused sampling), the monolithic prefill+pack program,
the per-job and batched chunk-prefill programs (fused Pallas kernel vs XLA
dispatch inside, `kernels.ops.use_prefill_kernel`), and the per-slot
``m_done`` finalize bookkeeping with its device mirrors.

Each jitted program is named by its inner function, so the profiler's
trace shows it as ``jit_<name>`` (``jit_mita_decode_step``,
``jit_mita_batched_chunk_prefill``, ...; docs/serving.md, Observability).

The scheduler sees none of it: it talks the `DecodeBackend` protocol
(`serve.backends`), and this module translates protocol calls into the
compiled programs documented in docs/serving.md.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import mita_decode as mdec
from repro.models import transformer as tfm
from repro.models.modules import ModelConfig
from repro.serve import spans
from repro.serve.backends import BackendBase


@functools.lru_cache(maxsize=None)
def _decode_fn(cfg: ModelConfig, fused_finalize: bool,
               fused_sampling: bool) -> Callable:
    """Fused whole-batch decode step, cached at module level so every
    backend instance with the same model config shares compiled code.

    Scheduler tensors (t, m_done, sample index) advance ON DEVICE: the hot
    loop uploads only the fed-back tokens — page tables, activity,
    positions, and per-request (rid, temperature) are re-uploaded solely
    when admission/retire changes them.  With ``fused_sampling`` the step
    also samples inside the program (`tfm.sample_tokens`) and returns [S]
    int32 tokens; otherwise it returns the [S, V] logits for the host
    sampler."""
    w = cfg.attn.window

    def mita_decode_step(p, st, tok, t, m_done, pt, ac, rid, si, temp, key):
        due = None
        if fused_finalize:
            due = ac & (t % w == 0) & (t // w > m_done)
            m_done = jnp.where(due, t // w, m_done)
        sample = (rid, si, temp, key) if fused_sampling else None
        out, st = tfm.lm_paged_decode_step(p, st, tok, t, pt, ac, cfg,
                                           due=due, sample=sample)
        adv = ac.astype(t.dtype)
        return out, st, t + adv, m_done, si + adv

    return jax.jit(mita_decode_step, donate_argnums=(1, 3, 4, 8))


@functools.lru_cache(maxsize=None)
def _prefill_pack_fn(cfg: ModelConfig, cap: int, k: int) -> Callable:
    """Fused batched prefill + pack-into-slots: one dispatch admits ``k``
    same-length requests (compiled per window-aligned capacity and group
    size).  Prefill rows are independent, so batching admissions does not
    change any request's tokens."""

    def mita_prefill_pack(p, st, toks, slots, pages):
        logits, pre = tfm.lm_prefill(p, toks, cfg, cap)
        for i in range(k):
            pre_i = jax.tree.map(
                lambda a: a[:, i:i + 1] if a.ndim >= 2 else a, pre)
            st = tfm.pack_prefill_into_states(st, pre_i, slots[i], pages[i],
                                              cfg)
        return logits, st

    return jax.jit(mita_prefill_pack, donate_argnums=(1,))


@functools.lru_cache(maxsize=None)
def _chunk_prefill_fn(cfg: ModelConfig, chunk: int, m_slot: int) -> Callable:
    """Per-job chunked prefill program (``prefill_mode="per-job"``): ONE
    compiled shape per (chunk length, pages-per-slot) serves every chunk of
    every request — resume point, validity, and the training/decode
    semantics boundary are data."""

    def mita_chunk_prefill(p, st, toks, slot, pt_row, t0, n_valid, n_train):
        return tfm.lm_prefill_chunk(p, st, toks, slot, pt_row, t0, n_valid,
                                    n_train, cfg)

    return jax.jit(mita_chunk_prefill, donate_argnums=(1,))


@functools.lru_cache(maxsize=None)
def _batched_chunk_prefill_fn(cfg: ModelConfig, chunk: int,
                              m_slot: int) -> Callable:
    """Batched chunked prefill program (``prefill_mode="batched"``, the
    default): EVERY currently-prefilling slot advances one chunk in ONE
    dispatch — which slots advance, their resume points, and validity are
    data, so the engine issues exactly one prefill dispatch per step no
    matter how many requests are mid-prefill.  Rows are packed to power-
    of-two widths; non-aligned prompts ride the same program (the n//m
    landmark quirk is per-slot data;
    `core.mita_decode.mita_batched_chunk_prefill`)."""

    def mita_batched_chunk_prefill(p, st, toks, job_active, pt, slots, t0,
                                   n_valid, n_train):
        return tfm.lm_prefill_chunks(p, st, toks, job_active, pt, slots,
                                     t0, n_valid, n_train, cfg)

    return jax.jit(mita_batched_chunk_prefill, donate_argnums=(1,))


@functools.lru_cache(maxsize=None)
def _draft_fn(cfg: ModelConfig, n_pos: int) -> Callable:
    """Self-drafting program: ``n_pos`` landmark-branch-only forward
    passes, each feeding its sampled token to the next (``lm_landmark_
    draft``).  Read-only — no donation, no state output: a rejected draft
    has nothing to undo."""

    def mita_draft(p, st, tok, t, ac, m_cnt, rid, si, temp, key):
        return tfm.lm_landmark_draft(p, st, tok, t, ac, m_cnt, cfg, n_pos,
                                     rid, si, temp, key)

    return jax.jit(mita_draft)


@functools.lru_cache(maxsize=None)
def _verify_fn(cfg: ModelConfig, fused_finalize: bool,
               n_pos: int) -> Callable:
    """Teacher-forced verify: ONE program scans the EXACT fused decode body
    (`_decode_fn`'s step, finalize cond and all) over the ``n_pos`` =
    spec_k + 1 positions [input, drafts...], sampling at every position.
    Collects the sampled tokens [n_pos, S] plus a per-position q_sum
    snapshot stack for `rollback` — the draft horizon guarantees the
    landmark finalize can only fire at position 0 (always committed), so
    the running query sum is the ONLY state a rejected suffix perturbs
    (appended KV rows past the commit point are masked by ``t`` and
    overwritten by future appends; no page churn)."""
    w = cfg.attn.window

    def mita_verify(p, st, toks, t, m_done, pt, ac, rid, si, temp, key,
                    spec_len):
        def body(carry, inp):
            st, t, m_done, si = carry
            i, tok = inp
            ac_i = ac & (i <= spec_len)
            due = None
            if fused_finalize:
                due = ac_i & (t % w == 0) & (t // w > m_done)
                m_done = jnp.where(due, t // w, m_done)
            out, st = tfm.lm_paged_decode_step(
                p, st, tok, t, pt, ac_i, cfg, due=due,
                sample=(rid, si, temp, key))
            adv = ac_i.astype(t.dtype)
            return (st, t + adv, m_done, si + adv), (out, st.q_sum)

        (st, _, _, _), (toks_out, q_stack) = jax.lax.scan(
            body, (st, t, m_done, si), (jnp.arange(n_pos), toks))
        return toks_out, q_stack, st

    return jax.jit(mita_verify, donate_argnums=(1,))


@functools.lru_cache(maxsize=None)
def _rollback_fn(cfg: ModelConfig) -> Callable:
    """Rewind the running query sums to the snapshot taken after the last
    committed verify position: per-slot gather of ``q_stack[commits - 1]``
    (commits >= 1 always — position 0 commits unconditionally; inactive
    slots pass commits=1, whose stack row equals their untouched sums
    because the verify scan's accumulate and finalize are active-masked)."""

    def mita_rollback(st, q_stack, commits):
        sel = jnp.moveaxis(q_stack, 2, 0)            # [S, k+1, L, Hkv, d]
        idx = (commits - 1)[:, None, None, None, None]
        picked = jnp.take_along_axis(sel, idx, axis=1)[:, 0]
        return st._replace(q_sum=jnp.moveaxis(picked, 0, 1))

    return jax.jit(mita_rollback, donate_argnums=(0,))


@functools.lru_cache(maxsize=None)
def _attach_prefix_fn(cfg: ModelConfig) -> Callable:
    """Install cached prefix summary rows into one slot: landmark
    queries/values, global expert rows and their validity, with both
    running query sums zeroed (a window-aligned resume point closes every
    window, so the cold engine's sums are exactly zero there too) and the
    prompt-side landmark queries mirroring the committed ones (for
    window-aligned prompts the two landmark systems share one grid —
    which is precisely why only aligned prefixes are cached).  One
    compiled shape per model config: the slot is data and rows beyond the
    attached prefix are zeros, masked by landmark availability exactly
    like a retired slot's stale rows."""

    def mita_attach_prefix(st, slot, lm_q, lm_v, ei, ev):
        zero = jnp.zeros(st.q_sum.shape[:1] + st.q_sum.shape[2:],
                         st.q_sum.dtype)
        return st._replace(
            lm_q=st.lm_q.at[:, slot].set(lm_q),
            lm_v=st.lm_v.at[:, slot].set(lm_v),
            expert_idx=st.expert_idx.at[:, slot].set(ei),
            expert_valid=st.expert_valid.at[:, slot].set(ev),
            pre_lm_q=st.pre_lm_q.at[:, slot].set(lm_q),
            q_sum=st.q_sum.at[:, slot].set(zero),
            pre_q_sum=st.pre_q_sum.at[:, slot].set(zero))

    return jax.jit(mita_attach_prefix, donate_argnums=(0,))


class MiTABackend(BackendBase):
    """Paged MiTA decode caches behind the `DecodeBackend` protocol."""

    name = "mita"
    supports_prefix_cache = True
    supports_speculation = True

    def __init__(self, params: Any, cfg: ModelConfig, ecfg: Any):
        from repro.kernels import ops
        super().__init__(params, cfg, ecfg)
        if cfg.attn.backend not in ("mita", "mita_ref"):
            raise ValueError("MiTABackend drives MiTA decode caches "
                             f"(got attention backend {cfg.attn.backend!r})")
        mode = getattr(ecfg, "spec_mode", "auto")
        if getattr(ecfg, "spec_k", 0) and mode not in ("auto", "landmark"):
            raise ValueError(
                f"MiTABackend speculates by self-drafting against the "
                f"compressed landmark branch (spec_mode='landmark'; got "
                f"{mode!r})")
        self.spec_mode = "landmark"
        # kernel→XLA VMEM fallbacks are counted process-wide at trace
        # time; this backend reports the deltas since it was built
        self._fallback_base = ops.fallback_counters()
        self._q_stack = None                  # verify→rollback handoff
        self.cfg = dataclasses.replace(
            cfg, attn=dataclasses.replace(
                cfg.attn, external_finalize=ecfg.finalize == "external"))
        self.window = cfg.attn.window
        s = ecfg.n_slots
        self.states = tfm.init_paged_states(self.cfg, s, ecfg.n_pages,
                                            ecfg.pages_per_slot)
        self.m_done = np.zeros(s, np.int32)   # finalized landmarks per slot
        # window-boundary landmark finalize fused behind a lax.cond —
        # off-boundary steps skip the O(context) work inside ONE program
        self._decode = _decode_fn(self.cfg, ecfg.finalize == "external",
                                  ecfg.sample_device == "fused")
        # device mirrors of the scheduler tensors (uploaded on change)
        self._t_dev = self._md_dev = self._pt_dev = self._ac_dev = None
        self._rid_dev = self._tp_dev = self._si_dev = None
        self._traceable: set[int] = set()     # validated prompt lengths

    # ------------------------------------------------------------ sizing --

    def chunkable(self, n_train: int, batched: bool) -> bool:
        """The batched chunk program serves any prompt (the n//m landmark
        quirk is per-slot data); the per-job program needs window-aligned
        prompts — the engine routes the rest through the monolithic head."""
        return batched or n_train % self.window == 0

    def validate_prompt(self, n: int, path: str) -> None:
        if path == "monolithic":
            self._check_prefill_traceable(n)
        elif n % self.window:
            # the chunk program replicates the training head's n//m
            # landmark pooling — representable only when m divides n
            # (pool1d's constraint, the same lengths the static path serves)
            if n % max(1, n // self.window):
                raise ValueError(
                    f"prompt length {n} is not servable by the chunked "
                    f"prefill path (window {self.window}): the training-"
                    "path landmark pooling needs n % (n // window) == 0")

    def _check_prefill_traceable(self, n: int) -> None:
        """Reject prompt lengths the prefill path cannot lower (e.g. the
        sorted-mita block_q divisibility constraint) at SUBMIT time, with
        abstract tracing only — a length that failed inside admission after
        scheduler state was mutated would leak the slot and its pages."""
        if n in self._traceable:
            return
        cap = mdec.window_aligned(n, self.window)
        mdl = self.cfg
        try:
            jax.eval_shape(
                lambda p, tok: tfm.lm_prefill(p, tok, mdl, cap),
                self.params,
                jax.ShapeDtypeStruct((1, n), jnp.int32))
        except Exception as e:
            raise ValueError(
                f"prompt length {n} is not servable by the "
                f"{mdl.attn.backend!r} prefill path (window {self.window}):"
                f" {e}") from e
        self._traceable.add(n)

    # ----------------------------------------------------------- prefill --

    def prefill_group(self, prompts: np.ndarray, slots: list[int],
                      pages_list: list[list[int]]) -> np.ndarray:
        k, n = prompts.shape
        cap = mdec.window_aligned(n, self.window)
        with spans.span("backend.prefill", rows=k, tokens=k * n):
            logits, self.states = _prefill_pack_fn(self.cfg, cap, k)(
                self.params, self.states, jnp.asarray(prompts, jnp.int32),
                jnp.asarray(slots, jnp.int32),
                jnp.asarray(np.stack([pg[: cap // self.window]
                                      for pg in pages_list]), jnp.int32))
            return spans.download(logits)

    def prefill_chunk(self, slot: int, pt_row: np.ndarray, toks: np.ndarray,
                      t0: int, n_valid: int, n_train: int) -> np.ndarray:
        fn = _chunk_prefill_fn(self.cfg, self.ecfg.prefill_chunk,
                               self.ecfg.pages_per_slot)
        with spans.span("backend.prefill", rows=1, tokens=n_valid):
            logits, self.states = fn(
                self.params, self.states, jnp.asarray(toks), np.int32(slot),
                jnp.asarray(pt_row), np.int32(t0), np.int32(n_valid),
                np.int32(n_train))
            self._count_prefill([n_valid])
            return spans.download(logits)

    def prefill_chunks(self, slot_ids: list[int], toks: np.ndarray,
                       job_active: np.ndarray, page_table: np.ndarray,
                       t0: np.ndarray, n_valid: np.ndarray,
                       n_train: np.ndarray) -> np.ndarray:
        fn = _batched_chunk_prefill_fn(self.cfg, self.ecfg.prefill_chunk,
                                       self.ecfg.pages_per_slot)
        valid = np.asarray(n_valid)[np.asarray(job_active)]
        with spans.span("backend.prefill", rows=len(valid),
                        tokens=int(valid.sum())):
            logits, self.states = fn(
                self.params, self.states, jnp.asarray(toks),
                jnp.asarray(job_active), jnp.asarray(page_table),
                jnp.asarray(slot_ids, jnp.int32).reshape(len(slot_ids)),
                jnp.asarray(t0), jnp.asarray(n_valid), jnp.asarray(n_train))
            self._count_prefill(valid)
            return spans.download(logits)

    # ------------------------------------------------------ slot lifecycle --

    def slot_filled(self, slot: int, n_tokens: int,
                    snapshot: Any = None) -> None:
        self.m_done[slot] = n_tokens // self.window
        self._dirty = True

    def preempt_snapshot(self, slot: int) -> Any:
        # recompute-from-prompt rebuilds the paged state bit-exactly
        # (`mita_chunk_prefill` replicates decode-time landmark
        # availability past the original prompt) — nothing to save
        return None

    # --------------------------------------------------------- prefix cache --

    def prefix_snapshot(self, slot: int, n_windows: int) -> list:
        """Host copies of the slot's first ``n_windows`` per-window summary
        rows — one (lm_q, lm_v, expert_idx, expert_valid) tuple per window,
        each [L, Hkv, ...] (the per-layer stack).  The expert rows are
        GLOBAL pool rows into the prefix's own pages, so they stay valid
        for every future holder of those pages — the radix cache's path
        invariant guarantees a node's pages outlive the node."""
        st = self.states
        lm_q, lm_v, ei, ev = jax.device_get(
            (st.lm_q[:, slot], st.lm_v[:, slot],
             st.expert_idx[:, slot], st.expert_valid[:, slot]))
        return [(lm_q[:, :, i].copy(), lm_v[:, :, i].copy(),
                 ei[:, :, i].copy(), ev[:, :, i].copy())
                for i in range(n_windows)]

    def attach_prefix(self, slot: int, payloads: list) -> None:
        """Make ``slot`` look exactly as if it had chunk-prefilled the
        cached windows itself: summary rows installed, query sums zeroed
        (window-aligned resume), pages arrive via the page table.  Padded
        to the full per-slot landmark capacity on the host so one jitted
        program (slot and rows are data) serves every hit."""
        st = self.states
        _, _, hkv, m_cap, d = st.lm_q.shape
        n_layers = st.lm_q.shape[0]
        k_w = st.expert_idx.shape[-1]
        lm_q = np.zeros((n_layers, hkv, m_cap, d), st.lm_q.dtype)
        lm_v = np.zeros((n_layers, hkv, m_cap, d), st.lm_v.dtype)
        ei = np.zeros((n_layers, hkv, m_cap, k_w), st.expert_idx.dtype)
        ev = np.zeros((n_layers, hkv, m_cap, k_w), bool)
        for i, (q_i, v_i, ei_i, ev_i) in enumerate(payloads):
            lm_q[:, :, i] = q_i
            lm_v[:, :, i] = v_i
            ei[:, :, i] = ei_i
            ev[:, :, i] = ev_i
        self.states = _attach_prefix_fn(self.cfg)(
            self.states, np.int32(slot), jnp.asarray(lm_q),
            jnp.asarray(lm_v), jnp.asarray(ei), jnp.asarray(ev))

    # ------------------------------------------------------------- decode --

    def decode_step(self, tokens_in: np.ndarray, t: np.ndarray,
                    active: np.ndarray, page_table: np.ndarray,
                    rid: np.ndarray, temperature: np.ndarray,
                    sample_idx: np.ndarray, key: jax.Array) -> np.ndarray:
        n_active = int(np.count_nonzero(active))
        with spans.span("backend.decode", slots=n_active):
            if self._dirty:
                self.mirror_uploads += 1
                with spans.span("backend.upload"):
                    # copies: on the CPU `jnp.asarray` may alias the
                    # engine's host arrays, which it updates in place
                    # between steps, and a mirror must hold what was
                    # uploaded, as it does on the TPU
                    self._t_dev = jnp.array(t)
                    self._md_dev = jnp.array(self.m_done)
                    self._pt_dev = jnp.array(page_table)
                    self._ac_dev = jnp.array(active)
                    self._rid_dev = jnp.array(rid)
                    self._tp_dev = jnp.array(temperature)
                    self._si_dev = jnp.array(sample_idx)
                self._dirty = False
            # host mirror of the device-side due/m_done transition
            w = self.window
            due = active & (t % w == 0) & (t // w > self.m_done)
            self.m_done = np.where(due, t // w, self.m_done)

            out, self.states, self._t_dev, self._md_dev, self._si_dev = \
                self._decode(self.params, self.states,
                             jnp.asarray(tokens_in), self._t_dev,
                             self._md_dev, self._pt_dev, self._ac_dev,
                             self._rid_dev, self._si_dev, self._tp_dev, key)
            self._count_decode(n_active)
            # fused sampling downloads [S] int32 tokens; the host path the
            # whole [S, V] logits (docs/serving.md, host-transfer budget)
            return spans.download(out)

    # -------------------------------------------------------- speculation --

    def draft_horizon(self, t: np.ndarray) -> np.ndarray:
        """Stop drafting short of the next landmark finalize so it can only
        fire at verify position 0 (which always commits): a rejected draft
        then never needs a landmark/expert/m_done rollback, and every
        speculative append stays inside the slot's current page — the one
        `_ensure_append_pages` guarantees.  With ``r = t % window``:
        external finalize fires when a position hits a window boundary;
        inline finalize fires one position earlier (it closes window
        ``(t+1) // w`` after the append), so at ``r == w - 1`` the round
        degenerates to plain decode — position ``t`` is the page's last
        row and drafting past it would append into an unowned page."""
        r = np.asarray(t) % self.window
        if self.cfg.attn.external_finalize:
            return np.where(r != 0, self.window - r - 1, self.window - 1)
        return np.where(r < self.window - 1, self.window - 2 - r, 0)

    def draft_steps(self, tokens_in: np.ndarray, t: np.ndarray,
                    active: np.ndarray, page_table: np.ndarray,
                    rid: np.ndarray, temperature: np.ndarray,
                    sample_idx: np.ndarray, key: jax.Array,
                    spec_len: np.ndarray) -> np.ndarray:
        # drafts attend ONLY the already-finalized landmark tiles — no
        # expert gather, no page-walk: page_table is unused, and the
        # landmark count is frozen at the round's start (external mode
        # drafts against the host m_done mirror; the position-0 finalize
        # lands in the verify step)
        ac = np.asarray(active) & (np.asarray(spec_len) > 0)
        m_cnt = (self.m_done.copy() if self.cfg.attn.external_finalize
                 else np.asarray(t) // self.window)
        drafts = _draft_fn(self.cfg, self.ecfg.spec_k)(
            self.params, self.states, jnp.asarray(tokens_in, jnp.int32),
            jnp.asarray(t), jnp.asarray(ac), jnp.asarray(m_cnt),
            jnp.asarray(rid), jnp.asarray(sample_idx),
            jnp.asarray(temperature), key)
        self._count_decode(np.count_nonzero(ac))
        return spans.download(drafts)

    def verify_step(self, tokens_in: np.ndarray, t: np.ndarray,
                    active: np.ndarray, page_table: np.ndarray,
                    rid: np.ndarray, temperature: np.ndarray,
                    sample_idx: np.ndarray, key: jax.Array,
                    spec_len: np.ndarray,
                    drafts: np.ndarray) -> np.ndarray:
        t = np.asarray(t)
        active = np.asarray(active)
        md_old = self.m_done.copy()
        if self.cfg.attn.external_finalize:
            # host mirror of the device transition: the draft horizon
            # guarantees finalize can only fire at position 0
            w = self.window
            due0 = active & (t % w == 0) & (t // w > self.m_done)
            self.m_done = np.where(due0, t // w, self.m_done)
        toks = np.concatenate(
            [np.asarray(tokens_in, np.int32)[None], np.asarray(drafts)], 0)
        fn = _verify_fn(self.cfg, self.cfg.attn.external_finalize,
                        self.ecfg.spec_k + 1)
        toks_out, self._q_stack, self.states = fn(
            self.params, self.states, jnp.asarray(toks, jnp.int32),
            jnp.asarray(t), jnp.asarray(md_old), jnp.asarray(page_table),
            jnp.asarray(active), jnp.asarray(rid),
            jnp.asarray(sample_idx), jnp.asarray(temperature), key,
            jnp.asarray(spec_len))
        self._count_decode(np.count_nonzero(active))
        return spans.download(toks_out)

    def rollback(self, commits: np.ndarray, active: np.ndarray) -> None:
        commits = np.where(np.asarray(active), np.asarray(commits), 1)
        self.states = _rollback_fn(self.cfg)(
            self.states, self._q_stack, jnp.asarray(commits, jnp.int32))
        self._q_stack = None

    def stats(self) -> dict:
        from repro.kernels import ops
        s = super().stats()
        now = ops.fallback_counters()
        s["prefill_kernel_fallbacks"] = (now["prefill"]
                                         - self._fallback_base["prefill"])
        s["paged_kernel_fallbacks"] = now["paged"] - self._fallback_base["paged"]
        s["finalize_kernel_fallbacks"] = (now["finalize"]
                                          - self._fallback_base["finalize"])
        return s

    # ------------------------------------------------------------- oracle --

    def static_reference(self, prompts: np.ndarray, max_new: int,
                         temperature: float = 0.0,
                         rids: Optional[list[int]] = None,
                         sample_key: Optional[jax.Array] = None
                         ) -> np.ndarray:
        """Static fixed-batch baseline at the slot capacity — the oracle
        the engine's greedy tokens are pinned against.  Greedy delegates
        to `launch.serve.static_generate` (the historical pin);
        ``temperature`` > 0 drives the same static programs step-by-step
        but samples with the engine's (rid, index)-keyed rule
        (`serve.backends.sample_host`), so tempered parity checks mean the
        same thing on every backend."""
        from repro.launch.serve import _static_fns, static_generate
        capacity = self.ecfg.pages_per_slot * self.window
        if temperature <= 0.0:
            gen, _ = static_generate(
                self.params, self.cfg, jnp.asarray(prompts, jnp.int32),
                max_new, capacity=capacity)
            return gen
        from repro.serve.backends import sample_host
        if sample_key is None:
            sample_key = jax.random.PRNGKey(0)
        b, n = prompts.shape
        rids = list(rids) if rids is not None else list(range(b))
        w = self.window
        prefill, decode, finalize = _static_fns(
            self.cfg, mdec.window_aligned(capacity, w))
        logits, states = prefill(self.params,
                                 jnp.asarray(prompts, jnp.int32))
        logits = np.asarray(logits)
        out = [[sample_host(logits[row], rids[row], 0, temperature,
                            sample_key)] for row in range(b)]
        m_done = n // w
        for i in range(1, max_new):
            pos = n + i - 1
            if self.cfg.attn.external_finalize and pos % w == 0 \
                    and pos // w > m_done:
                states = finalize(states)
                m_done = pos // w
            tok = jnp.asarray([o[-1] for o in out], jnp.int32)
            logits, states = decode(self.params, states, tok,
                                    jnp.asarray(pos))
            logits = np.asarray(logits)
            for row in range(b):
                out[row].append(sample_host(logits[row], rids[row], i,
                                            temperature, sample_key))
        return np.asarray(out, np.int32)
