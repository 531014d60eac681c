"""Serving backends: the `DecodeBackend` protocol behind the scheduler.

`repro.serve.engine.ServingEngine` is a GENERIC continuous-batching
scheduler: admission, the priority queue, preemption, chunked-prefill
pacing, page accounting, sampling bookkeeping, and stats never mention a
model family — every device-side operation goes through a `DecodeBackend`.
A backend owns the model parameters, the per-slot decode state, its device
mirrors, and every compiled program; the engine owns requests, slots,
pages, and time.

Page semantics are backend-defined: the MiTA backend's pages are real pool
rows (a page = ``window`` KV/landmark rows, named by per-slot page tables);
the recurrent backends' states are constant-size per slot, so pages are
pure admission-control currency — ``pages_needed`` still meters context
budget, which keeps priority preemption and the allocator's fairness
ordering meaningful across the whole fast-weight spectrum (the paper's
framing: routing → compression; docs/serving.md §Backend protocol).

Protocol (duck-typed; `BackendBase` supplies the defaults):

  * ``fresh()``                 — new instance, zeroed state (warmup scratch).
  * ``pages_needed(n)``         — pages covering an ``n``-token context.
  * ``chunkable(n, batched)``   — can the chunk program serve a fresh
                                  ``n``-token prompt (False → the engine
                                  routes it through ``prefill_group``)?
  * ``validate_prompt(n, path)``— raise at SUBMIT time if the path
                                  ("monolithic" | "chunked") cannot lower
                                  this length; nothing may be mutated.
  * ``alloc_slot(slot)``        — a slot was assigned: prepare its state
                                  (recurrent backends zero the accumulator).
  * ``prefill_group(...)``      — monolithic prefill+pack of an admission
                                  group, one dispatch.
  * ``prefill_chunk(...)``      — advance ONE job one chunk (per-job mode).
  * ``prefill_chunks(...)``     — advance EVERY packed job row one chunk in
                                  one dispatch (batched mode).
  * ``slot_filled(slot, n, snapshot)`` — the slot enters the decode batch
                                  with ``n`` tokens of context.
  * ``decode_step(...)``        — one fused step for the whole slot batch;
                                  returns [S, V] logits (host sampling) or
                                  [S] sampled tokens (fused sampling).
  * ``retire(slot)``            — the slot left the decode batch.
  * ``preempt_snapshot(slot)``  — capture what re-admission needs beyond
                                  recompute-from-prompt (None for both
                                  current families: recompute is exact).
  * ``invalidate()``            — host copies of scheduler tensors changed;
                                  re-upload device mirrors next step.
  * ``stats()``                 — per-backend counters (dispatches,
                                  active slots and prefill rows/tokens
                                  dispatched, mirror rebuilds, kernel
                                  fallbacks) merged into
                                  ``ServingEngine.stats()``.
  * ``static_reference(...)``   — the backend's static/full-forward oracle;
                                  engine greedy tokens must be bit-identical.
  * ``supports_prefix_cache``   — True if pages hold real per-token context
                                  a prefix cache can share by reference
                                  (False → the engine silently runs
                                  cache-off; recurrent states fold the
                                  whole prefix into one accumulator, so
                                  there is nothing page-resident to reuse).
  * ``prefix_snapshot(slot, m)``— host copies of the slot's first ``m``
                                  per-window summary payloads, stored in
                                  the radix cache next to the page ids.
  * ``attach_prefix(slot, payloads)`` — install cached payloads so the
                                  slot's state is exactly what prefilling
                                  those windows itself would have produced
                                  (the pages attach via the page table).
  * ``supports_speculation``    — True if the backend implements the
                                  draft/verify/rollback triple below
                                  (``EngineConfig.spec_k > 0`` requires it).
  * ``draft_horizon(t)``        — per-slot cap on how many tokens may be
                                  drafted past position ``t`` before a
                                  backend-internal boundary (the MiTA
                                  backend stops short of the next landmark
                                  finalize so a rejected draft never needs
                                  a landmark/expert rollback).
  * ``draft_steps(...)``        — cheaply propose up to ``spec_len[s]``
                                  tokens per slot ([k, S]); MUST NOT change
                                  any state a rejected draft would need
                                  undone beyond what ``rollback`` restores.
  * ``verify_step(...)``        — run the EXACT decode rule over the k+1
                                  positions [input, drafts...] and return
                                  the tokens it samples ([k+1, S]); the
                                  engine commits the longest exact-match
                                  prefix + one correction.
  * ``rollback(commits, active)``— rewind per-slot state to exactly
                                  ``commits[s]`` tokens past the round's
                                  start — bit-identical to having decoded
                                  those tokens one step at a time.
  * ``on_quarantine(slots)`` / ``on_degrade(level)`` / ``on_stall()`` —
                                  supervision notifications (no-op
                                  defaults); `serve/supervisor.py` fires
                                  them on fault isolation, a degradation-
                                  ladder rung, and scheduler stalls, and
                                  fault-injection wrappers
                                  (`serve/chaos.py`) key fault lifecycles
                                  off them.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.mita_decode import window_aligned

# THE stats schema: every `ServingEngine.stats()` dict holds exactly these
# keys — the engine's scheduler counters plus the backend counters every
# `BackendBase.stats()` reports.  Bench JSON rows and the conformance suite
# pin against these sets instead of three ad-hoc copies drifting apart.
ENGINE_STAT_KEYS = frozenset({
    "backend", "steps", "chunks", "prefill_dispatches", "preemptions",
    "pages_high_water", "reserve_dips", "prefix_cache_hits",
    "prefix_cache_misses", "pages_shared", "prefix_tokens_reused",
    "prefix_cache_pages", "prefix_cache_evictions",
    "spec_drafted", "spec_accepted", "spec_rollbacks",
    "rejected", "deadline_expired", "retries", "quarantined",
    "degradation_level",
})
BACKEND_STAT_KEYS = frozenset({
    "decode_dispatches", "decode_slot_steps", "prefill_rows",
    "prefill_tokens", "mirror_uploads", "prefill_kernel_fallbacks",
    "paged_kernel_fallbacks", "finalize_kernel_fallbacks",
})
STATS_SCHEMA = ENGINE_STAT_KEYS | BACKEND_STAT_KEYS


def sample_host(logits, rid: int, index: int, temperature: float,
                key) -> int:
    """THE host-side sampling rule, shared by the engine's hot loop and
    every backend's `static_reference` so the engine==reference parity
    gates compare one recipe, not three copies: greedy first-index argmax,
    or a categorical keyed by fold_in(fold_in(key, rid), index) with the
    same 1e-6 temperature floor as the fused on-device sampler
    (`models.transformer.sample_tokens`) — tokens are therefore identical
    across host/fused sampling and invariant to batching, slot placement,
    and preemption schedule."""
    if temperature <= 0.0:
        return int(np.argmax(logits))
    k = jax.random.fold_in(jax.random.fold_in(key, rid), index)
    return int(jax.random.categorical(
        k, jnp.asarray(logits) / max(temperature, 1e-6)))


class BackendBase:
    """Shared defaults: window-quantized page math, no-op lifecycle hooks.

    Subclasses must set ``name``, ``window``, and implement the prefill /
    decode entry points; ``model_cfg``/``params``/``ecfg`` are kept so
    ``fresh()`` can rebuild an identically-configured instance (compiled
    programs are cached module-wide, so a fresh instance recompiles
    nothing)."""

    name = "backend"
    supports_prefix_cache = False
    supports_speculation = False

    def __init__(self, params: Any, cfg: Any, ecfg: Any):
        self.params = params
        self.model_cfg = cfg
        self.ecfg = ecfg
        self.decode_dispatches = 0
        self.decode_slot_steps = 0      # active slots, summed per dispatch
        self.prefill_rows = 0           # chunk-prefill job rows
        self.prefill_tokens = 0         # ...and their valid prompt tokens
        self.mirror_uploads = 0         # decode steps that rebuilt mirrors
        self._dirty = True

    def fresh(self) -> "BackendBase":
        return type(self)(self.params, self.model_cfg, self.ecfg)

    def pages_needed(self, n_tokens: int) -> int:
        return window_aligned(n_tokens, self.window) // self.window

    def chunkable(self, n_train: int, batched: bool) -> bool:
        return True

    def validate_prompt(self, n: int, path: str) -> None:
        pass

    def alloc_slot(self, slot: int) -> None:
        pass

    def slot_filled(self, slot: int, n_tokens: int,
                    snapshot: Any = None) -> None:
        pass

    def retire(self, slot: int) -> None:
        pass

    def preempt_snapshot(self, slot: int) -> Any:
        return None

    def prefix_snapshot(self, slot: int, n_windows: int) -> list:
        raise NotImplementedError(
            f"{self.name} backend does not support the prefix cache")

    def attach_prefix(self, slot: int, payloads: list) -> None:
        raise NotImplementedError(
            f"{self.name} backend does not support the prefix cache")

    # --- speculative decoding (EngineConfig.spec_k > 0) ------------------
    # A backend advertises `supports_speculation = True` and implements the
    # triple; the engine owns accept/reject bookkeeping and never calls
    # these on a backend that does not advertise them.

    def draft_horizon(self, t: np.ndarray) -> np.ndarray:
        """Per-slot cap on draftable tokens past position ``t`` ([S] ->
        [S]).  Default: no backend-internal boundary, draft freely."""
        return np.full_like(np.asarray(t), np.iinfo(np.int32).max)

    def draft_steps(self, tokens_in, t, active, page_table, rid,
                    temperature, sample_idx, key, spec_len) -> np.ndarray:
        raise NotImplementedError(
            f"{self.name} backend does not support speculative decoding")

    def verify_step(self, tokens_in, t, active, page_table, rid,
                    temperature, sample_idx, key, spec_len,
                    drafts) -> np.ndarray:
        raise NotImplementedError(
            f"{self.name} backend does not support speculative decoding")

    def rollback(self, commits: np.ndarray, active: np.ndarray) -> None:
        raise NotImplementedError(
            f"{self.name} backend does not support speculative decoding")

    def invalidate(self) -> None:
        self._dirty = True

    def _count_decode(self, n_active) -> None:
        """One decode-side dispatch over ``n_active`` active slots."""
        self.decode_dispatches += 1
        self.decode_slot_steps += int(n_active)

    def _count_prefill(self, n_valid) -> None:
        """One chunk-prefill dispatch: ``n_valid`` holds each job row's
        valid prompt tokens."""
        self.prefill_rows += len(n_valid)
        self.prefill_tokens += int(np.sum(n_valid))

    # --- supervision hooks (serve/supervisor.py) -------------------------
    # No-op by default: the supervisor notifies the backend of fault-
    # isolation events so wrappers (serve/chaos.py) can key fault
    # lifecycles off them — quarantine clears slot-bound faults, a ladder
    # rung clears persistent ones, a stall drains held resources.

    def on_quarantine(self, slots: list) -> None:
        pass

    def on_degrade(self, level: int) -> None:
        pass

    def on_stall(self) -> None:
        pass

    def stats(self) -> dict:
        # the fallback counters are process-global and MiTA-kernel-
        # specific; backends that never dispatch those kernels report 0
        # rather than inheriting another engine's trace-time fallbacks
        # (keys must cover BACKEND_STAT_KEYS exactly)
        return {"decode_dispatches": self.decode_dispatches,
                "decode_slot_steps": self.decode_slot_steps,
                "prefill_rows": self.prefill_rows,
                "prefill_tokens": self.prefill_tokens,
                "mirror_uploads": self.mirror_uploads,
                "prefill_kernel_fallbacks": 0,
                "paged_kernel_fallbacks": 0,
                "finalize_kernel_fallbacks": 0}


def resolve(params: Any, cfg: Any, ecfg: Any) -> BackendBase:
    """Default backend for a bare `ModelConfig` (the engine's ctor path
    when no backend is passed): the paged MiTA backend.  Recurrent
    architectures carry no marker on `ModelConfig` alone — build them via
    `for_arch` (the registry's family field decides)."""
    attn = getattr(getattr(cfg, "attn", None), "backend", None)
    if attn in ("mita", "mita_ref"):
        from repro.serve.backends.mita import MiTABackend
        return MiTABackend(params, cfg, ecfg)
    raise ValueError(
        f"no default serving backend for attention backend {attn!r}: "
        "ServingEngine drives MiTA paged decode caches unless a backend is "
        "passed — ssm/hybrid architectures serve through "
        "serve.backends.for_arch (constant-size recurrent slot states)")


def for_arch(arch: Any, params: Any, ecfg: Any) -> BackendBase:
    """Backend for a registry `ArchConfig` — any architecture with a decode
    state is servable through the same scheduler."""
    if arch.family in ("dense", "moe", "vlm"):
        from repro.serve.backends.mita import MiTABackend
        return MiTABackend(params, arch.model, ecfg)
    if arch.family == "ssm":
        from repro.serve.backends.recurrent import Mamba2Backend
        return Mamba2Backend(params, arch.model, ecfg)
    if arch.family == "hybrid":
        from repro.serve.backends.recurrent import RGLRUBackend
        return RGLRUBackend(params, arch.model, ecfg)
    raise ValueError(f"family {arch.family!r} has no serving backend "
                     "(encdec decode is capacity-448 native; see registry)")


__all__ = ["BackendBase", "resolve", "for_arch", "sample_host",
           "ENGINE_STAT_KEYS", "BACKEND_STAT_KEYS", "STATS_SCHEMA"]
