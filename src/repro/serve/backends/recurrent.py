"""Recurrent serving backends: Mamba2 (SSD) and RecurrentGemma (RG-LRU).

These are the compression end of the paper's fast-weight spectrum: the
decode state is a CONSTANT-size module per request (SSD state + conv tail;
RG-LRU state + conv tail + a bounded per-slot attention cache for the
hybrid's attention layers), so a "slot" is an index into the state's batch
axis and no paging indirection exists.  The scheduler's pages become pure
admission-control currency — `pages_needed` still meters context budget,
which keeps priority preemption, the reserve, and the allocator fairness
order meaningful across backends.

Program inventory (mirroring the paged backend's three-program shape):

  * ``decode``  — one fused step for the whole slot batch; per-slot
    positions, activity, and sampling inputs are data.  State updates are
    masked by activity (`core.slotted.where_slots`), so an idle slot's
    state is bit-frozen.
  * ``chunk``   — `*_prefill_chunk`: a sequential scan of the EXACT
    decode-step update over one fixed-shape chunk for a row-packed subset
    of slots (`core.slotted.gather_slots` / `scatter_slots`; inactive rows
    pass through bit-identically).  ONE compiled shape per (chunk length,
    row width) serves every chunk at any resume point — which is what
    makes recompute-from-prompt preemption exact: re-scanning prompt +
    emitted tokens rebuilds the state the victim had when evicted.
  * ``monolithic`` — the same chunk program at the window-aligned prompt
    capacity (one dispatch per admission group), used when the engine runs
    unchunked.

In the profiler's trace they are ``jit_recurrent_decode_step`` and
``jit_recurrent_chunk_prefill``; speculation adds ``jit_recurrent_draft``
and ``jit_recurrent_teacher_forced``.

The static reference (`static_reference`) is a STRUCTURALLY different
program — a time-major `lax.scan` of the full decode step over the prompt,
then single-token decode — so engine==reference greedy parity checks the
slot scatter/gather, masking, and chunking machinery, not a program
against itself.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import slotted
from repro.core.mita_decode import window_aligned
from repro.models import mamba2 as m2
from repro.models import rglru as rg
from repro.models import transformer as tfm
from repro.serve import spans
from repro.serve.backends import BackendBase, sample_host

# family -> (init_states(cfg, n_slots, capacity), decode(p, st, tok, pos,
# cfg), chunk(p, st, toks, t0, n_valid, cfg)); states are stacked pytrees
# with the slot axis second (leaves [L, S, ...])
_OPS: dict[str, tuple[Callable, Callable, Callable]] = {
    "mamba2": (lambda cfg, s, cap: m2.mamba_slot_states(cfg, s),
               m2.mamba_decode_step, m2.mamba_prefill_chunk),
    "rglru": (rg.rg_slot_states, rg.rg_slot_decode_step, rg.rg_prefill_chunk),
}


_zero_slot = jax.jit(slotted.zero_slot, donate_argnums=(0,))


@functools.lru_cache(maxsize=None)
def _decode_fn(family: str, cfg, fused_sampling: bool) -> Callable:
    """Fused whole-slot-batch decode step: model step + activity-masked
    state commit + on-device position/sample-index advance (+ fused
    sampling).  Cached module-wide so engines sharing a config share
    compiled code."""
    _, decode_raw, _ = _OPS[family]

    def recurrent_decode_step(p, st, tok, t, ac, rid, si, temp, key):
        logits, st_new = decode_raw(p, st, tok, t, cfg)
        st = slotted.where_slots(ac, st_new, st, axis=1)
        adv = ac.astype(t.dtype)
        if fused_sampling:
            out = tfm.sample_tokens(logits, rid, si, temp, key)
        else:
            out = logits
        return out, st, t + adv, si + adv

    return jax.jit(recurrent_decode_step, donate_argnums=(1, 3, 6))


@functools.lru_cache(maxsize=None)
def _chunk_fn(family: str, cfg) -> Callable:
    """Row-packed chunk scan: gather the rows' slot states, scan the chunk,
    scatter back (rows with n_valid == 0 scatter their gathered values —
    bit-identical).  Jit caches one program per (chunk length, row width)."""
    _, _, chunk_raw = _OPS[family]

    def recurrent_chunk_prefill(p, st, slot_ids, toks, t0, n_valid):
        sub = slotted.gather_slots(st, slot_ids)
        logits, sub = chunk_raw(p, sub, toks, t0, n_valid, cfg)
        return logits, slotted.scatter_slots(st, slot_ids, sub)

    return jax.jit(recurrent_chunk_prefill, donate_argnums=(1,))


@functools.lru_cache(maxsize=None)
def _spec_draft_fn(family: str, cfg, n_pos: int) -> Callable:
    """Free-running speculative scan (``spec_mode="self"``): the EXACT
    decode body over ``n_pos`` positions in ONE dispatch, each sampled
    token fed to the next, per-slot length as data (positions past
    ``spec_len[s]`` pass the carry through bit-frozen).  The state commits
    through the scan — self-drafted tokens ARE the decode rule's output,
    so every draft verifies and no rollback exists on this path; the win
    is dispatch collapse: one program commits up to ``n_pos`` tokens."""
    _, decode_raw, _ = _OPS[family]

    def recurrent_draft(p, st, tok, t, ac, rid, si, temp, key, spec_len):
        def body(carry, i):
            st, tok, t, si = carry
            ac_i = ac & (i < spec_len)
            logits, st_new = decode_raw(p, st, tok, t, cfg)
            st = slotted.where_slots(ac_i, st_new, st, axis=1)
            tok2 = tfm.sample_tokens(logits, rid, si, temp, key)
            tok2 = jnp.where(ac_i, tok2, tok)
            adv = ac_i.astype(t.dtype)
            return (st, tok2, t + adv, si + adv), tok2

        (st, _, _, _), drafts = jax.lax.scan(body, (st, tok, t, si),
                                             jnp.arange(n_pos))
        return drafts, st

    return jax.jit(recurrent_draft, donate_argnums=(1,))


@functools.lru_cache(maxsize=None)
def _spec_tf_fn(family: str, cfg, n_pos: int) -> Callable:
    """Teacher-forced speculative scan (``spec_mode="stress"`` verify AND
    rollback replay): consume a fixed [n_pos, S] token stream through the
    exact decode body, per-slot step count as data, collecting the sampled
    tokens.  The same compiled program serves both calls — verify runs it
    over [input, drafts...] with ``n_steps = spec_len + 1``; rollback
    restores the pre-verify snapshot and re-runs it over the COMMITTED
    stream with ``n_steps = commits``, which is bit-identical to having
    decoded those tokens one step at a time (the committed prefix of the
    verify scan consumed exactly these inputs from the same state)."""
    _, decode_raw, _ = _OPS[family]

    def recurrent_teacher_forced(p, st, toks, t, ac, rid, si, temp, key,
                                 n_steps):
        def body(carry, inp):
            st, t, si = carry
            i, tok = inp
            ac_i = ac & (i < n_steps)
            logits, st_new = decode_raw(p, st, tok, t, cfg)
            st = slotted.where_slots(ac_i, st_new, st, axis=1)
            out = tfm.sample_tokens(logits, rid, si, temp, key)
            adv = ac_i.astype(t.dtype)
            return (st, t + adv, si + adv), out

        (st, _, _), outs = jax.lax.scan(body, (st, t, si),
                                        (jnp.arange(n_pos), toks))
        return outs, st

    return jax.jit(recurrent_teacher_forced, donate_argnums=(1,))


# snapshot for the stress path's rollback; scans donate their state input,
# so the copy must NOT (fresh buffers, original untouched)
_tree_copy = jax.jit(lambda st: jax.tree.map(jnp.copy, st))


@functools.lru_cache(maxsize=None)
def _ref_prefill_fn(family: str, cfg, n: int) -> Callable:
    """Reference prefill: time-major scan of the FULL decode step over the
    prompt — a different program structure from the serving chunk scan, so
    parity gates test the machinery, not a program against itself."""
    _, decode_raw, _ = _OPS[family]

    def run(p, st, toks):                       # toks: [B, n]
        b = toks.shape[0]

        def step(st, inp):
            tok, pos = inp
            logits, st = decode_raw(p, st, tok, jnp.full((b,), pos), cfg)
            return st, logits

        st, logits = jax.lax.scan(step, st, (toks.T, jnp.arange(n)))
        return logits[-1], st

    return jax.jit(run, donate_argnums=(1,))


@functools.lru_cache(maxsize=None)
def _ref_step_fn(family: str, cfg) -> Callable:
    _, decode_raw, _ = _OPS[family]
    return jax.jit(lambda p, st, tok, pos: decode_raw(p, st, tok, pos, cfg),
                   donate_argnums=(1,))


class _RecurrentBackend(BackendBase):
    """Shared `DecodeBackend` implementation over `_OPS[family]`."""

    family = ""
    supports_speculation = True

    def __init__(self, params: Any, cfg: Any, ecfg: Any):
        super().__init__(params, cfg, ecfg)
        mode = getattr(ecfg, "spec_mode", "auto")
        self.spec_mode = "self" if mode == "auto" else mode
        if getattr(ecfg, "spec_k", 0) and self.spec_mode not in ("self",
                                                                 "stress"):
            raise ValueError(
                f"recurrent backends speculate by self-drafting through "
                f"the decode scan (spec_mode='self') or via the synthetic "
                f"rollback-exercising 'stress' mode (got {mode!r})")
        # inline landmark finalize for the hybrid's attention caches: the
        # slot-wise vmap evaluates both cond branches anyway, and inline
        # semantics make the chunk-scan prefill and the decode step the
        # same per-token function — the exactness recompute rests on
        self.cfg = dataclasses.replace(
            cfg, attn=dataclasses.replace(cfg.attn, external_finalize=False))
        self.window = cfg.attn.window
        self.capacity = ecfg.pages_per_slot * self.window
        init, _, _ = _OPS[self.family]
        self.states = init(self.cfg, ecfg.n_slots, self.capacity)
        self._decode = _decode_fn(self.family, self.cfg,
                                  ecfg.sample_device == "fused")
        self._t_dev = self._ac_dev = self._rid_dev = None
        self._tp_dev = self._si_dev = None
        self._snap = None                  # stress verify→rollback handoff
        self._verify_toks = self._stress = None

    # ------------------------------------------------------ slot lifecycle --

    def alloc_slot(self, slot: int) -> None:
        # the chunk scan accumulates into the slot's state from zero — a
        # retired occupant's state must not leak into the new request
        self.states = _zero_slot(self.states, np.int32(slot))

    # ----------------------------------------------------------- prefill --

    def prefill_group(self, prompts: np.ndarray, slots: list[int],
                      pages_list: list[list[int]]) -> np.ndarray:
        del pages_list                  # constant-size states: no pages
        k, n = prompts.shape
        nc = window_aligned(n, self.window)
        toks = np.zeros((k, nc), np.int32)
        toks[:, :n] = prompts
        with spans.span("backend.prefill", rows=k, tokens=k * n):
            logits, self.states = _chunk_fn(self.family, self.cfg)(
                self.params, self.states, jnp.asarray(slots, jnp.int32),
                jnp.asarray(toks), jnp.zeros(k, jnp.int32),
                jnp.full(k, n, jnp.int32))
            return spans.download(logits)

    def prefill_chunk(self, slot: int, pt_row: np.ndarray, toks: np.ndarray,
                      t0: int, n_valid: int, n_train: int) -> np.ndarray:
        return self.prefill_chunks(
            [slot], toks[None], np.ones(1, bool), pt_row[None],
            np.array([t0], np.int32), np.array([n_valid], np.int32),
            np.array([n_train], np.int32))[0]

    def prefill_chunks(self, slot_ids: list[int], toks: np.ndarray,
                       job_active: np.ndarray, page_table: np.ndarray,
                       t0: np.ndarray, n_valid: np.ndarray,
                       n_train: np.ndarray) -> np.ndarray:
        del page_table                  # constant-size states: no pages
        del n_train                     # no train/decode semantics boundary:
        #                                 the chunk IS the decode update, so
        #                                 recomputed generated positions are
        #                                 exact by construction
        nv = np.where(job_active, n_valid, 0).astype(np.int32)
        valid = nv[np.asarray(job_active)]
        with spans.span("backend.prefill", rows=len(valid),
                        tokens=int(valid.sum())):
            logits, self.states = _chunk_fn(self.family, self.cfg)(
                self.params, self.states, jnp.asarray(slot_ids, jnp.int32),
                jnp.asarray(toks), jnp.asarray(t0, dtype=jnp.int32),
                jnp.asarray(nv))
            self._count_prefill(valid)
            return spans.download(logits)

    # ------------------------------------------------------------- decode --

    def decode_step(self, tokens_in: np.ndarray, t: np.ndarray,
                    active: np.ndarray, page_table: np.ndarray,
                    rid: np.ndarray, temperature: np.ndarray,
                    sample_idx: np.ndarray, key: jax.Array) -> np.ndarray:
        del page_table                  # constant-size states: no pages
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
                    self._ac_dev = jnp.array(active)
                    self._rid_dev = jnp.array(rid)
                    self._tp_dev = jnp.array(temperature)
                    self._si_dev = jnp.array(sample_idx)
                self._dirty = False
            out, self.states, self._t_dev, self._si_dev = self._decode(
                self.params, self.states, jnp.asarray(tokens_in),
                self._t_dev, self._ac_dev, self._rid_dev, self._si_dev,
                self._tp_dev, key)
            self._count_decode(n_active)
            return spans.download(out)

    # -------------------------------------------------------- speculation --

    def draft_steps(self, tokens_in: np.ndarray, t: np.ndarray,
                    active: np.ndarray, page_table: np.ndarray,
                    rid: np.ndarray, temperature: np.ndarray,
                    sample_idx: np.ndarray, key: jax.Array,
                    spec_len: np.ndarray) -> np.ndarray:
        del page_table                  # constant-size states: no pages
        k = self.ecfg.spec_k
        if self.spec_mode == "stress":
            # synthetic host-side proposals, deliberately (mostly) wrong:
            # zero dispatches here, and the verify/rollback pair below gets
            # exercised with real mismatches — the conformance suite's way
            # of pinning rollback bit-exactness on a backend whose natural
            # speculation never rejects
            off = np.arange(1, k + 1, dtype=np.int32)[:, None]
            return ((np.asarray(tokens_in, np.int32)[None] + off)
                    % self.cfg.vocab)
        drafts, self.states = _spec_draft_fn(self.family, self.cfg, k)(
            self.params, self.states, jnp.asarray(tokens_in, jnp.int32),
            jnp.asarray(t), jnp.asarray(active), jnp.asarray(rid),
            jnp.asarray(sample_idx), jnp.asarray(temperature), key,
            jnp.asarray(spec_len))
        self._count_decode(np.count_nonzero(active))
        return spans.download(drafts)

    def verify_step(self, tokens_in: np.ndarray, t: np.ndarray,
                    active: np.ndarray, page_table: np.ndarray,
                    rid: np.ndarray, temperature: np.ndarray,
                    sample_idx: np.ndarray, key: jax.Array,
                    spec_len: np.ndarray,
                    drafts: np.ndarray) -> np.ndarray:
        del page_table                  # constant-size states: no pages
        k = self.ecfg.spec_k
        tokens_in = np.asarray(tokens_in, np.int32)
        t = np.asarray(t)
        active = np.asarray(active)
        spec_len = np.asarray(spec_len)
        sample_idx = np.asarray(sample_idx)
        if self.spec_mode == "stress":
            # snapshot (the scan donates the LIVE state, not the copy),
            # then teacher-force [input, drafts...] through the decode
            # scan; rollback restores + replays the committed prefix with
            # the same inputs, stashed here
            self._snap = _tree_copy(self.states)
            # private copies: rollback is dispatched with no read-back,
            # and the engine advances its own t / sample_idx / active in
            # place right after it returns.  JAX reads a host array when
            # its transfer runs, which can be after the computations
            # queued ahead of it, so a shared array would race the update
            self._stress = (tokens_in.copy(), t.copy(), np.array(rid),
                            np.array(temperature), sample_idx.copy(), key)
            toks = np.concatenate([tokens_in[None], np.asarray(drafts)], 0)
            outs, self.states = _spec_tf_fn(self.family, self.cfg, k + 1)(
                self.params, self.states, jnp.asarray(toks, jnp.int32),
                jnp.asarray(t), jnp.asarray(active), jnp.asarray(rid),
                jnp.asarray(sample_idx), jnp.asarray(temperature), key,
                jnp.asarray(spec_len + 1))
            self._count_decode(np.count_nonzero(active))
            self._verify_toks = spans.download(outs)
            return self._verify_toks
        # self mode: the draft scan already ran the exact decode rule and
        # committed its state, so the drafts verify themselves; one more
        # masked decode step at t0 + spec_len samples the correction token
        s = len(tokens_in)
        rows = np.maximum(spec_len - 1, 0)
        tok_v = np.where(spec_len > 0,
                         np.asarray(drafts)[rows, np.arange(s)], tokens_in)
        self._dirty = True              # mirrors must see spec'd t/si
        corr = self.decode_step(
            tok_v.astype(np.int32), t + spec_len, active, None, rid,
            temperature, sample_idx + spec_len, key)
        self._dirty = True              # ...and forget them afterwards
        verify = np.concatenate(
            [np.asarray(drafts), np.zeros((1, s), np.int32)], 0)
        verify[spec_len, np.arange(s)] = corr
        return verify

    def rollback(self, commits: np.ndarray, active: np.ndarray) -> None:
        if self.spec_mode == "self":
            return                      # drafted state IS the decode state
        tokens_in, t, rid, temp, sample_idx, key = self._stress
        active = np.array(active)       # copied, as in `verify_step`
        n = np.where(active, np.asarray(commits), 0)
        # the committed prefix of the verify scan consumed exactly
        # [input, verify[0..c-2]] — replaying that stream from the
        # snapshot is bit-identical to having decoded it step by step
        toks = np.concatenate([tokens_in[None], self._verify_toks[:-1]], 0)
        _, self.states = _spec_tf_fn(self.family, self.cfg,
                                     self.ecfg.spec_k + 1)(
            self.params, self._snap, jnp.asarray(toks, jnp.int32),
            jnp.asarray(t), jnp.asarray(active), jnp.asarray(rid),
            jnp.asarray(sample_idx), jnp.asarray(temp), key,
            jnp.asarray(n, jnp.int32))
        self._count_decode(np.count_nonzero(active))
        self._snap = self._verify_toks = self._stress = None

    # ------------------------------------------------------------- oracle --

    def static_reference(self, prompts: np.ndarray, max_new: int,
                         temperature: float = 0.0,
                         rids: Optional[list[int]] = None,
                         sample_key: Optional[jax.Array] = None
                         ) -> np.ndarray:
        """Full-forward reference: time-major prompt scan + single-token
        decode, batch-independent per lane.  Greedy by default; with
        ``temperature`` > 0, keys derive from (rid, token index) exactly
        like the engine's sampler, so tokens stay schedule-invariant."""
        b, n = prompts.shape
        if sample_key is None:
            sample_key = jax.random.PRNGKey(0)
        rids = list(rids) if rids is not None else list(range(b))
        init, _, _ = _OPS[self.family]
        states = init(self.cfg, b, self.capacity)
        logits, states = _ref_prefill_fn(self.family, self.cfg, n)(
            self.params, states, jnp.asarray(prompts, jnp.int32))
        step = _ref_step_fn(self.family, self.cfg)

        def sample(lg, row, index):
            return sample_host(lg, rids[row], index, temperature,
                               sample_key)

        logits = np.asarray(logits)
        out = [[sample(logits[row], row, 0)] for row in range(b)]
        for i in range(1, max_new):
            tok = jnp.asarray([o[-1] for o in out], jnp.int32)
            logits, states = step(self.params, states, tok,
                                  jnp.full((b,), n + i - 1, jnp.int32))
            logits = np.asarray(logits)
            for row in range(b):
                out[row].append(sample(logits[row], row, i))
        return np.asarray(out, np.int32)


class Mamba2Backend(_RecurrentBackend):
    """SSD decode state per slot: h [H, P, S] + conv tail — the paper
    taxonomy's compressed fast-weight module as a servable backend."""

    name = family = "mamba2"


class RGLRUBackend(_RecurrentBackend):
    """RecurrentGemma hybrid: RG-LRU recurrences + a bounded per-slot
    attention cache advanced at per-slot positions
    (`models.transformer.attention_decode_slots`)."""

    name = family = "rglru"
