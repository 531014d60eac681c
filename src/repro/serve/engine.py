"""Continuous-batching serving engine — a backend-agnostic scheduler.

The scheduler is plain host Python and never touches a device tensor:
admission, the priority queue, preemption, chunked-prefill pacing, page
accounting, sampling bookkeeping, and stats are generic over the
`DecodeBackend` protocol (`repro.serve.backends`).  A backend owns the
model parameters, the per-slot decode state, its device mirrors, and every
compiled program; the engine owns requests, slots, pages, and time.
docs/serving.md documents the protocol, the request lifecycle, and each
backend's program inventory.

Per engine step the backend is asked for at most three dispatches:

  * ``prefill_group``   — monolithic prefill of an admission group packed
    straight into the group's slots (``prefill_chunk = 0``);
  * ``prefill_chunks``  — ONE program advancing EVERY currently-prefilling
    slot's chunk per step (batched mode; ``prefill_chunk`` > 0); long
    prompts then admit incrementally, interleaved with the decode batch,
    instead of stalling it.  ``prefill_mode = "per-job"`` keeps the legacy
    one-job-per-step dispatch (``prefill_chunk``);
  * ``decode_step``     — ONE program for the whole slot batch regardless
    of per-request progress (per-slot positions, page tables, and activity
    are data, not shape).  With ``sample_device == "fused"`` sampling runs
    inside the program and the hot loop downloads [S] int32 tokens instead
    of [S, V] logits.

Pages are the scheduler's admission-control currency; whether a page is a
real pool region (the paged-attention backend) or pure context-budget
accounting (constant-size recurrent states) is the backend's business.

Chunked mode also enables priority preemption: under page pressure the
scheduler evicts the lowest-priority victim (releasing its pages) and later
rebuilds it by chunk-prefilling prompt + generated-so-far — recompute-from-
prompt, vLLM-style.  A preempted request emits the same greedy tokens it
would have emitted unpreempted (`tests/test_serve_chunked.py` and
`tests/test_serve_backends.py` pin this per backend).

Greedy sampling is exact w.r.t. each backend's static reference: a request
decoded by the engine emits the same tokens it would emit in a fixed batch
(`tests/test_serve.py` pins this).  Temperature sampling derives its key
from (request id, token index) so results are batching-invariant too.
"""

from __future__ import annotations

import bisect
import dataclasses
import time
from typing import Any, Optional

import jax
import numpy as np

from repro.serve import backends as _backends
from repro.serve import spans


class AllocatorInvariantError(RuntimeError):
    """Page accounting corruption: double-free, duplicate release, retain
    of a free page, or an allocation the caller failed to guard with
    `can_alloc`.  These are scheduler bugs, not workload conditions — the
    supervisor re-raises them instead of retrying (`serve/supervisor.py`),
    and no admission-control path may convert them into a rejection."""


@dataclasses.dataclass(eq=False)
class Request:
    """One generation job.

    Shape contract: ``prompt`` is a [n] int32 token array with n >= 1;
    ``max_new_tokens`` >= 1 counts every emitted token INCLUDING the first
    one sampled from the prefill logits, so a request occupies
    ``ceil((n + max_new_tokens) / window)`` pages at full length.

    ``priority``: higher wins.  Admission order is (priority desc, submit
    order); in chunked mode a higher-priority arrival may preempt the
    lowest-priority running request under page pressure (the victim is
    rebuilt later, emitting identical tokens).

    ``eq=False``: requests compare by identity — the scheduler removes them
    from its queue by object, and a generated __eq__ would compare the
    ndarray prompt."""
    rid: int
    prompt: np.ndarray              # [n] int32 token ids
    max_new_tokens: int
    temperature: float = 0.0
    arrival: float = 0.0            # seconds since trace start
    priority: int = 0               # higher = more important
    deadline_ms: Optional[float] = None   # wall-clock SLO from submit


@dataclasses.dataclass
class FinishedRequest:
    """``arrival`` is trace-relative (copied from the Request); all other
    stamps are absolute `time.perf_counter` values.  ``preemptions`` counts
    how many times the request was evicted and rebuilt.

    ``cancelled``: the request was killed by `ServingEngine.cancel` —
    ``tokens`` holds whatever was emitted before the kill (possibly
    nothing), and a request cancelled while still waiting carries zeroed
    admission/TTFT stamps.

    ``reason`` is the structured finish taxonomy (`FINISH_REASONS`):
    ``"complete"`` ran to max_new_tokens; ``"cancelled"`` was killed by
    `cancel`; ``"deadline_expired"`` missed its ``deadline_ms`` SLO (a
    cancel with its own label — ``cancelled`` is True for both);
    ``"rejected"`` was shed at submit time (typed backpressure: the
    request can never fit a slot or no prefill path can serve it) and
    never entered the scheduler."""
    rid: int
    tokens: np.ndarray              # [max_new_tokens] generated ids
    arrival: float
    admitted: float                 # when prefill started
    first_token: float              # TTFT reference point
    finished: float
    token_times: list[float] = dataclasses.field(default_factory=list)
    preemptions: int = 0
    cancelled: bool = False
    reason: str = "complete"


FINISH_REASONS = ("complete", "cancelled", "deadline_expired", "rejected")


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Slot/page budget and scheduling knobs.

    Invariants enforced at construction: the pool minus the reserve still
    fits one slot's maximum context (otherwise admission could deadlock),
    and ``prefill_chunk`` is a positive multiple of the backend's window
    (pages are window-quantized, so chunk boundaries must be too).

    ``prefill_chunk`` = 0 (default) keeps the monolithic prefill path:
    full page budget up front, no preemption — exactly the PR-1 engine.
    ``prefill_chunk`` > 0 enables chunked prefill AND priority preemption:
    requests admit with their first chunk's pages only, grow page-by-page,
    and may be evicted for higher-priority work.

    ``reserve_pages``: pages the admission/prefill path may not claim;
    only decode-time appends (one page per ``window`` tokens per slot) can
    dip into them, which is what keeps running requests running when a
    burst of admissions would otherwise drain the pool.

    ``finalize``: backend-interpreted decode-time bookkeeping mode.  For
    the paged-attention backend, "external" runs the window-boundary
    summary update as part of the fused step only when due (the default)
    and "inline" folds it into every step; constant-size recurrent
    backends have no deferred work and ignore it.

    ``sample_device``: where decode-time sampling runs.  ``"host"``
    downloads the [S, V] logits every step and samples in Python;
    ``"fused"`` samples inside the decode program and downloads [S] int32
    tokens — same greedy argmax, same (rid, index)-derived categorical
    keys, so tokens are bit-identical across the two modes.

    ``prefill_mode`` (chunked mode only): ``"batched"`` (default) advances
    EVERY prefilling slot one chunk per step in ONE fused dispatch (a slot
    mask, same compiled shape regardless of how many slots are prefilling);
    ``"per-job"`` is the legacy baseline — at most one job advances one
    chunk per step in its own dispatch, and prompts the backend's chunk
    program cannot start from scratch take the monolithic path.

    ``prefix_cache`` (chunked mode only): keep a radix cache of committed
    window-aligned prompt prefixes, keyed by token content.  An incoming
    prompt whose leading windows match a cached prefix attaches those
    pages by reference (ref-counted, read-only) plus the per-window
    summary rows the backend snapshotted when the prefix was first
    computed, and its chunked prefill skips straight to the first
    unshared chunk — TTFT collapses for shared-system-prompt traffic.
    Backends that do not store per-token context in pages have nothing to
    reuse and silently run cache-off.  Cached pages are reclaimed, LRU
    leaf first, before the scheduler resorts to preempting live work.

    ``spec_k`` > 0 enables LOSSLESS speculative decoding: each engine step
    becomes one draft/verify/commit round — the backend cheaply proposes up
    to ``spec_k`` tokens per slot (`draft_steps`), re-derives all of them
    plus one correction through its exact decode rule in one fused
    teacher-forced pass (`verify_step`), and the engine commits the longest
    draft prefix the verification reproduced plus the first corrected
    token, rewinding backend state past the commit point (`rollback`).
    Emitted streams are bit-identical to ``spec_k = 0`` at any temperature
    (verification samples with the same (rid, index)-derived keys), across
    preemption, cancellation, and the prefix cache.  Requires
    ``sample_device="fused"`` and a backend advertising
    ``supports_speculation``.  ``spec_mode`` selects the backend's drafting
    strategy ("auto" picks its native one: the paged MiTA backend drafts
    against the compressed landmark branch only; recurrent backends run
    their exact decode scan — also accepting "stress", the synthetic
    wrong-draft mode that exercises rollback)."""
    n_slots: int = 8                # decode batch width
    n_pages: int = 64               # shared pool size (pages of `window`)
    pages_per_slot: int = 8         # max context per request, in pages
    finalize: str = "external"      # external | inline (backend-specific)
    prefill_chunk: int = 0          # chunk length (0 = monolithic prefill)
    reserve_pages: int = 0          # appends-only page reserve
    sample_device: str = "host"     # host | fused (on-device sampling)
    prefill_mode: str = "batched"   # batched | per-job (chunk dispatch)
    prefix_cache: bool = False      # shared-prefix reuse (chunked only)
    spec_k: int = 0                 # speculative tokens/round (0 = off)
    spec_mode: str = "auto"         # backend drafting strategy


class _PageAllocator:
    """Ref-counted free-list over the shared pool.

    A page leaves the free list with one reference (`alloc`); additional
    holders `retain` it (prefix sharing: a cached prefix node and every
    slot reading it each hold one reference) and every holder `release`s
    it — the page returns to the free list only when the LAST reference
    drops.  Releasing a free or never-retained page, or the same page
    twice in one call, is a hard error: with shared pages a silent
    double-free would hand one holder's live page to a new owner, which is
    state corruption, not mis-accounting.

    ``reserve`` pages are invisible to ordinary allocations (admission,
    prefill chunks) and only served when ``reserved=True`` (decode appends)
    — the high-water mark and the dip counter quantify how close the pool
    came to starving the decode batch."""

    def __init__(self, n_pages: int, reserve: int = 0):
        self.n_pages = n_pages
        self.reserve = reserve
        self.free: list[int] = list(range(n_pages))
        self.refs: dict[int, int] = {}  # page id -> live reference count
        self.high_water = 0             # max pages ever in use
        self.reserve_dips = 0           # appends served from the reserve

    @property
    def in_use(self) -> int:
        return self.n_pages - len(self.free)

    def refcount(self, page: int) -> int:
        return self.refs.get(page, 0)

    @property
    def shared_pages(self) -> int:
        """Pages currently held by more than one reference."""
        return sum(1 for c in self.refs.values() if c > 1)

    def can_alloc(self, n: int, reserved: bool = False) -> bool:
        avail = len(self.free) if reserved else len(self.free) - self.reserve
        return n <= avail

    def alloc(self, n: int, reserved: bool = False) -> list[int]:
        if not self.can_alloc(n, reserved):
            raise AllocatorInvariantError("page pool exhausted")
        if reserved and len(self.free) - n < self.reserve:
            self.reserve_dips += 1
        pages, self.free = self.free[:n], self.free[n:]
        for p in pages:
            self.refs[p] = 1
        self.high_water = max(self.high_water, self.in_use)
        return pages

    def retain(self, pages: list[int]) -> None:
        """Add one reference to each (already-allocated) page."""
        for p in pages:
            if self.refs.get(p, 0) < 1:
                raise AllocatorInvariantError(
                    f"retain of page {p} which is not allocated")
        for p in pages:
            self.refs[p] += 1

    def release(self, pages: list[int]) -> None:
        """Drop one reference per page; free pages whose count hits zero.

        Validates the whole batch before mutating anything, so a raising
        call never half-applies."""
        if len(set(pages)) != len(pages):
            raise AllocatorInvariantError(
                f"release with duplicate page ids: {sorted(pages)}")
        for p in pages:
            if self.refs.get(p, 0) < 1:
                raise AllocatorInvariantError(
                    f"double-free: page {p} has no live reference")
        for p in pages:
            self.refs[p] -= 1
            if self.refs[p] == 0:
                del self.refs[p]
                self.free.append(p)


@dataclasses.dataclass(eq=False)
class _WaitEntry:
    """Queue entry: (priority desc, submit order) defines admission order.
    ``resume`` holds (tokens, times, meta) for a preempted request awaiting
    its recompute-from-prompt re-admission; ``snapshot`` is the backend's
    opaque `preempt_snapshot` payload handed back at `slot_filled`;
    ``evictions`` counts every preemption the request has suffered
    (mid-prefill restarts included).  ``first_admit`` is the stamp of the
    FIRST admission — a preempted victim (mid-prefill ones included, which
    carry no ``resume``) must report its original admission time, not the
    re-admission's, or TTFT under-reports queueing delay for exactly the
    requests that suffered most."""
    req: Request
    seq: int
    resume: Optional[tuple] = None
    snapshot: Any = None
    evictions: int = 0
    first_admit: Optional[float] = None

    @property
    def key(self):
        return (-self.req.priority, self.seq)


@dataclasses.dataclass(eq=False)
class _PrefillJob:
    """A request mid-(chunked)-prefill: owns a slot and a growing page set,
    but is NOT in the decode batch until the last chunk lands."""
    entry: _WaitEntry
    toks: np.ndarray                # prompt [+ generated-so-far] to pack
    n_train: int                    # original prompt length (semantics)
    admit_time: float
    done: int = 0                   # tokens packed so far (next chunk's t0)


class ServingEngine:
    """Admit/evict requests each step; keep the fused decode batch full."""

    def __init__(self, params: Any, cfg: Any,
                 ecfg: EngineConfig = EngineConfig(),
                 sample_key: jax.Array | None = None,
                 backend: Optional[Any] = None):
        if ecfg.finalize not in ("external", "inline"):
            raise ValueError(f"unknown finalize mode {ecfg.finalize!r}")
        if ecfg.n_pages - ecfg.reserve_pages < ecfg.pages_per_slot:
            raise ValueError("pool minus reserve smaller than one slot's "
                             "max context — admission could deadlock")
        if ecfg.reserve_pages < 0:
            raise ValueError("reserve_pages must be >= 0")
        if ecfg.sample_device not in ("host", "fused"):
            raise ValueError(f"unknown sample_device {ecfg.sample_device!r}")
        if ecfg.prefill_mode not in ("batched", "per-job"):
            raise ValueError(f"unknown prefill_mode {ecfg.prefill_mode!r}")
        if ecfg.prefix_cache and not ecfg.prefill_chunk:
            raise ValueError("prefix_cache requires chunked prefill "
                             "(prefill_chunk > 0): cache hits resume the "
                             "chunk program at the first unshared chunk")
        if ecfg.spec_k < 0:
            raise ValueError("spec_k must be >= 0")
        self.backend = (backend if backend is not None
                        else _backends.resolve(params, cfg, ecfg))
        if ecfg.spec_k:
            if ecfg.sample_device != "fused":
                raise ValueError(
                    "speculative decoding samples inside the verify "
                    "program (spec_k > 0 requires sample_device='fused')")
            if not getattr(self.backend, "supports_speculation", False):
                raise ValueError(
                    f"the {self.backend.name!r} backend does not support "
                    "speculative decoding (spec_k > 0)")
        self.params = params
        self.cfg = cfg
        self.ecfg = ecfg
        self.w = self.backend.window
        if ecfg.prefill_chunk and (ecfg.prefill_chunk < 0
                                   or ecfg.prefill_chunk % self.w):
            raise ValueError("prefill_chunk must be a positive multiple of "
                             f"the backend window ({self.w})")
        self._key = (jax.random.PRNGKey(0) if sample_key is None
                     else sample_key)

        s, m = ecfg.n_slots, ecfg.pages_per_slot
        self.alloc = _PageAllocator(ecfg.n_pages, ecfg.reserve_pages)

        # host-owned scheduler state
        self.page_table = np.zeros((s, m), np.int32)
        self.t = np.zeros(s, np.int32)
        self.active = np.zeros(s, bool)
        self.tokens_in = np.zeros(s, np.int32)
        # per-slot sampling inputs for the fused on-device sampler
        self.slot_rid = np.zeros(s, np.int32)
        self.slot_temp = np.zeros(s, np.float32)
        self.sample_idx = np.zeros(s, np.int32)   # next token index per slot
        self.free_slots: list[int] = list(range(s))
        self.slot_req: dict[int, Request] = {}
        self.slot_entry: dict[int, _WaitEntry] = {}
        self.slot_pages: dict[int, list[int]] = {}
        self.slot_out: dict[int, list[int]] = {}
        self.slot_times: dict[int, list[float]] = {}
        self.slot_meta: dict[int, tuple[float, float]] = {}  # admitted, ttft
        self.slot_seq: dict[int, int] = {}    # admission recency (victims)
        self.slot_npre: dict[int, int] = {}   # preemptions suffered so far
        self.prefilling: dict[int, _PrefillJob] = {}
        self.waiting: list[_WaitEntry] = []   # sorted by _WaitEntry.key
        self.finished: list[FinishedRequest] = []
        self.steps = 0
        self.n_preemptions = 0
        self.n_chunks = 0
        self.prefill_dispatches = 0
        self.step_times: list[float] = []
        self._seq = 0
        self._inflight: set[int] = set()    # rids waiting or active

        # prefix cache (opt-in; silently off for backends with nothing
        # page-resident to reuse) + its counters, zero when disabled
        self.cache = None
        if ecfg.prefix_cache and getattr(self.backend,
                                         "supports_prefix_cache", False):
            from repro.serve.prefix_cache import RadixPrefixCache
            self.cache = RadixPrefixCache(self.alloc, self.w)
        self.n_prefix_hits = 0
        self.n_prefix_misses = 0
        self.n_pages_shared = 0           # pages attached by reference
        self.n_prefix_tokens_reused = 0   # prompt tokens never re-prefilled
        self.prefix_hits: dict[int, int] = {}  # rid -> tokens reused

        # speculative-decoding counters (zero when spec_k == 0)
        self.n_spec_drafted = 0           # draft tokens proposed
        self.n_spec_accepted = 0          # draft tokens verification kept
        self.n_spec_rollbacks = 0         # rounds that rejected a draft

        # robustness counters (serve/supervisor.py increments retries /
        # quarantined / degradation_level; rejections and deadline kills
        # are the engine's own admission-control outcomes)
        self.n_rejected = 0               # requests shed at submit
        self.n_deadline_expired = 0       # requests killed past their SLO
        self.n_retries = 0                # supervised step re-executions
        self.n_quarantined = 0            # slots evicted by fault isolation
        self.degradation_level = 0        # supervisor ladder rung (0 = full)
        self._deadline: dict[int, float] = {}  # rid -> absolute expiry
        self.reject_reasons: dict[int, str] = {}  # rid -> why it was shed

    # ------------------------------------------------------------ plumbing --

    def _sample(self, logits: np.ndarray, req: Request, index: int) -> int:
        # ONE host sampling rule shared with every backend's
        # static_reference (and bit-matched by the fused on-device
        # sampler) — the parity gates compare a single recipe
        return _backends.sample_host(logits, req.rid, index,
                                     req.temperature, self._key)

    def pages_needed(self, req: Request) -> int:
        return self.backend.pages_needed(len(req.prompt)
                                         + req.max_new_tokens)

    def warmup(self, prompt_lens: list[int]) -> None:
        """Compile every program the serving loop can hit for the given
        prompt lengths: the fused decode step, the chunk-prefill program
        variants (chunked mode: per-job has one; batched has one per
        power-of-two row width, exercised by submitting that many probes
        at once so they prefill concurrently), and each monolithic prefill
        variant.  Runs on one scratch engine so this engine's
        pool/scheduler state is untouched (compile caches are shared
        module-wide)."""
        scratch = ServingEngine(self.params, self.cfg, self.ecfg,
                                backend=self.backend.fresh())
        k_max = 1 if (self.ecfg.prefill_chunk
                      and self.ecfg.prefill_mode == "per-job") \
            else self.ecfg.n_slots
        if self.ecfg.prefill_chunk and self.ecfg.prefill_mode == "batched":
            # no compiled program depends on prompt length in batched
            # chunked mode (length and resume point are data) — one
            # representative length covers every width variant
            prompt_lens = [max(prompt_lens)] if prompt_lens else []
        for n in sorted(set(prompt_lens)):
            # probe requests claim the MINIMAL page budget a real request
            # of this length would (max_new=1), so warmup never rejects a
            # length the engine can actually serve
            gen = 2 if self.backend.pages_needed(n + 2) \
                <= self.ecfg.pages_per_slot else 1
            sizes = []
            k = 1
            while k <= k_max:
                sizes.append(k)
                k *= 2
            if sizes[-1] != k_max:
                # non-power-of-two slot counts cap the batched prefill row
                # width at k_max itself — compile that variant too
                sizes.append(k_max)
            for k in sizes:
                scratch.run([Request(rid=-1 - i, prompt=np.zeros(n, np.int32),
                                     max_new_tokens=gen) for i in range(k)])

    def stats(self) -> dict[str, Any]:
        """Scheduler counters: fused steps, prefill chunks run (per slot),
        prefill dispatches issued (batched mode: ≤ 1 per step regardless of
        how many slots are prefilling), preemptions, and the allocator's
        high-water / reserve accounting — merged with the backend's own
        counters (decode dispatches, kernel fallbacks)."""
        s = {"backend": self.backend.name,
             "steps": self.steps, "chunks": self.n_chunks,
             "prefill_dispatches": self.prefill_dispatches,
             "preemptions": self.n_preemptions,
             "pages_high_water": self.alloc.high_water,
             "reserve_dips": self.alloc.reserve_dips,
             "prefix_cache_hits": self.n_prefix_hits,
             "prefix_cache_misses": self.n_prefix_misses,
             "pages_shared": self.n_pages_shared,
             "prefix_tokens_reused": self.n_prefix_tokens_reused,
             "prefix_cache_pages": (self.cache.n_pages
                                    if self.cache is not None else 0),
             "prefix_cache_evictions": (self.cache.evictions
                                        if self.cache is not None else 0),
             "spec_drafted": self.n_spec_drafted,
             "spec_accepted": self.n_spec_accepted,
             "spec_rollbacks": self.n_spec_rollbacks,
             "rejected": self.n_rejected,
             "deadline_expired": self.n_deadline_expired,
             "retries": self.n_retries,
             "quarantined": self.n_quarantined,
             "degradation_level": self.degradation_level}
        s.update(self.backend.stats())
        return s

    # ----------------------------------------------------------- scheduler --

    def submit(self, req: Request) -> bool:
        """Queue a request, or shed it.  Returns True when queued.

        Malformed submissions (empty prompt, max_new < 1, a rid already in
        flight) are caller bugs and still raise ValueError.  Workload
        conditions the engine can never serve — prompt + max_new exceeding
        a slot's page budget, or a prompt length no prefill path can lower
        — are STRUCTURED BACKPRESSURE, not errors: the request is shed
        with a ``FinishedRequest(reason="rejected")`` (tokens empty, rid
        free for resubmission), ``n_rejected`` counts it, and False is
        returned.  Nothing downstream of a True return can reject: an
        admitted request can always finish (invariant 3)."""
        if len(req.prompt) < 1 or req.max_new_tokens < 1:
            raise ValueError("need a non-empty prompt and ≥ 1 new token")
        if req.rid in self._inflight:
            raise ValueError(f"request id {req.rid} is already in flight")
        try:
            self._validate_servable(req)
        except ValueError as e:
            self._reject(req, str(e))
            return False
        self._inflight.add(req.rid)
        self._seq += 1
        self._enqueue(_WaitEntry(req=req, seq=self._seq))
        if req.deadline_ms is not None:
            self._deadline[req.rid] = (time.perf_counter()
                                       + req.deadline_ms / 1e3)
        return True

    def _validate_servable(self, req: Request) -> None:
        """Raise ValueError when no admission path can ever serve ``req``
        — before any scheduler state is touched."""
        if self.pages_needed(req) > self.ecfg.pages_per_slot:
            raise ValueError(
                f"request {req.rid} needs {self.pages_needed(req)} pages; a "
                f"slot owns {self.ecfg.pages_per_slot} "
                f"(max context {self.ecfg.pages_per_slot * self.w})")
        n = len(req.prompt)
        batched = self.ecfg.prefill_mode == "batched"
        if not self.ecfg.prefill_chunk:
            self.backend.validate_prompt(n, "monolithic")
        elif self.backend.chunkable(n, batched):
            self.backend.validate_prompt(n, "chunked")
        elif batched:
            # batched chunked mode has no monolithic route — shed now
            # rather than feed the chunk program a prompt the backend
            # said it cannot start (unreachable for the current backends,
            # which chunk everything in batched mode)
            raise ValueError(
                f"prompt length {n} is not servable: the "
                f"{self.backend.name} backend cannot start it through the "
                "batched chunk program (use prefill_mode='per-job' or "
                "monolithic prefill)")
        else:
            self.backend.validate_prompt(n, "monolithic")

    def _reject(self, req: Request, why: str) -> None:
        self.n_rejected += 1
        self.reject_reasons[req.rid] = why
        now = time.perf_counter()
        self.finished.append(FinishedRequest(
            rid=req.rid, tokens=np.zeros(0, np.int32), arrival=req.arrival,
            admitted=0.0, first_token=0.0, finished=now,
            reason="rejected"))

    def _enqueue(self, entry: _WaitEntry) -> None:
        bisect.insort(self.waiting, entry, key=lambda e: e.key)

    def _emit(self, slot: int, tok: int, now: float) -> None:
        self.slot_out[slot].append(tok)
        self.slot_times[slot].append(now)

    def _retire(self, slot: int, now: float, cancelled: bool = False,
                reason: Optional[str] = None) -> None:
        if reason is None:
            reason = "cancelled" if cancelled else "complete"
        req = self.slot_req.pop(slot)
        self.slot_entry.pop(slot)
        out = self.slot_out.pop(slot)
        times = self.slot_times.pop(slot)
        admitted, ttft = self.slot_meta.pop(slot)
        self.alloc.release(self.slot_pages.pop(slot))
        self.slot_seq.pop(slot)
        npre = self.slot_npre.pop(slot)
        self.active[slot] = False
        self.t[slot] = 0
        self.page_table[slot] = 0     # unused entries must stay in-bounds
        # a stale temperature would defeat the fused sampler's all-greedy
        # fast path (sample_tokens conds on "any slot tempered")
        self.slot_temp[slot] = 0.0
        self.free_slots.append(slot)
        self.backend.retire(slot)
        self.backend.invalidate()
        self._inflight.discard(req.rid)
        self.finished.append(FinishedRequest(
            rid=req.rid, tokens=np.asarray(out, np.int32),
            arrival=req.arrival, admitted=admitted, first_token=ttft,
            finished=now, token_times=times, preemptions=npre,
            cancelled=cancelled, reason=reason))

    def cancel(self, rid: int, reason: str = "cancelled") -> bool:
        """Kill an in-flight request in ANY state — waiting (fresh or
        preempted-awaiting-readmission), mid-chunked-prefill, or decoding —
        releasing its slot and page references immediately and emitting a
        ``cancelled`` FinishedRequest carrying whatever tokens were already
        out.  ``reason`` labels the kill ("cancelled", or
        "deadline_expired" when the engine's own SLO sweep fires it).
        Returns False if the rid is not in flight (already finished,
        never submitted, or cancelled twice)."""
        now = time.perf_counter()
        for entry in self.waiting:
            if entry.req.rid == rid:
                self.waiting.remove(entry)
                out, times, meta = entry.resume or \
                    ([], [], (entry.first_admit or 0.0, 0.0))
                self._inflight.discard(rid)
                self.finished.append(FinishedRequest(
                    rid=rid, tokens=np.asarray(out, np.int32),
                    arrival=entry.req.arrival, admitted=meta[0],
                    first_token=meta[1], finished=now,
                    token_times=list(times), preemptions=entry.evictions,
                    cancelled=True, reason=reason))
                return True
        for slot, job in self.prefilling.items():
            if job.entry.req.rid != rid:
                continue
            entry = job.entry
            del self.prefilling[slot]
            self.alloc.release(self.slot_pages.pop(slot))
            self.slot_seq.pop(slot)
            self.page_table[slot] = 0
            self.free_slots.append(slot)
            self.backend.retire(slot)
            self.backend.invalidate()
            self._inflight.discard(rid)
            out, times, meta = entry.resume or \
                ([], [], (job.admit_time, 0.0))
            self.finished.append(FinishedRequest(
                rid=rid, tokens=np.asarray(out, np.int32),
                arrival=entry.req.arrival, admitted=meta[0],
                first_token=meta[1], finished=now, token_times=list(times),
                preemptions=entry.evictions, cancelled=True, reason=reason))
            return True
        for slot, req in self.slot_req.items():
            if req.rid == rid:
                self._retire(slot, now, cancelled=True, reason=reason)
                return True
        return False

    def _expire_deadlines(self) -> None:
        """Cancel every in-flight request whose ``deadline_ms`` SLO has
        passed, with the ``deadline_expired`` finish reason — the kill
        rides the ordinary `cancel` path, so slot and page release follow
        the exact lifecycle cancellation already pins."""
        if not self._deadline:
            return
        now = time.perf_counter()
        for rid, expiry in list(self._deadline.items()):
            if rid not in self._inflight:
                del self._deadline[rid]
            elif now >= expiry:
                del self._deadline[rid]
                if self.cancel(rid, reason="deadline_expired"):
                    self.n_deadline_expired += 1

    # ---------------------------------------------------------- preemption --

    def _pick_victim(self, below: Optional[int] = None) -> Optional[int]:
        """Lowest-priority occupied slot; ties broken toward the most
        recently admitted (its recompute loses the least work).  ``below``
        restricts candidates to strictly lower priorities (admission-side
        preemption never thrashes equals)."""
        cands = [(job.entry.req.priority, self.slot_seq[s], s)
                 for s, job in self.prefilling.items()]
        cands += [(req.priority, self.slot_seq[s], s)
                  for s, req in self.slot_req.items()]
        if below is not None:
            cands = [c for c in cands if c[0] < below]
        if not cands:
            return None
        cands.sort(key=lambda c: (c[0], -c[1]))
        return cands[0][2]

    def _preempt(self, slot: int) -> None:
        """Evict ``slot``: release its pages and requeue its request.  A
        decoding victim keeps its emitted tokens/stamps (plus the backend's
        snapshot) and is rebuilt by recompute-from-prompt; a prefilling
        victim simply restarts (it has emitted nothing)."""
        self.n_preemptions += 1
        self.alloc.release(self.slot_pages.pop(slot))
        self.page_table[slot] = 0
        self.slot_seq.pop(slot)
        job = self.prefilling.pop(slot, None)
        if job is not None:
            entry = job.entry      # mid-prefill: restart, nothing emitted
        else:
            entry = self.slot_entry.pop(slot)
            self.slot_req.pop(slot)
            out = self.slot_out.pop(slot)
            times = self.slot_times.pop(slot)
            meta = self.slot_meta.pop(slot)
            self.slot_npre.pop(slot)
            entry.resume = (out, times, meta)
            entry.snapshot = self.backend.preempt_snapshot(slot)
            self.active[slot] = False
            self.t[slot] = 0
            self.slot_temp[slot] = 0.0
            self.backend.invalidate()
        entry.evictions += 1
        self.free_slots.append(slot)
        self._enqueue(entry)

    def _reclaim_cache(self, pages: int, reserved: bool = False) -> None:
        """Drop cached prefix nodes (LRU leaf first) until ``pages`` are
        allocatable or the cache is empty.  Runs BEFORE any preemption
        path considers live victims: cached pages are spare capacity, and
        a cache-only reference is always cheaper to sacrifice than a
        running request's recompute."""
        if self.cache is None:
            return
        while (not self.alloc.can_alloc(pages, reserved)
               and self.cache.evict_one()):
            pass

    def _preempt_for(self, priority: int, pages: int,
                     need_slot: bool = False) -> None:
        """Evict strictly-lower-priority victims until ``pages`` are
        allocatable (and a slot is free, if requested) or none remain.
        Cached prefix pages are reclaimed before any victim is touched."""
        self._reclaim_cache(pages)
        while ((need_slot and not self.free_slots)
               or not self.alloc.can_alloc(pages)):
            victim = self._pick_victim(below=priority)
            if victim is None:
                return
            self._preempt(victim)
            self._reclaim_cache(pages)

    # ----------------------------------------------------------- admission --

    def _admit(self, now: float) -> list[int]:
        """Admit what fits; returns the admitted request ids."""
        if self.ecfg.prefill_chunk:
            return self._admit_chunked(now)
        return self._admit_grouped(now)

    def _entry_total(self, entry: _WaitEntry) -> int:
        """Tokens the prefill of this entry must pack: the prompt, plus
        (for a preempted victim's recompute) everything it had emitted
        short of the last token, which re-enters through decode."""
        n_train = len(entry.req.prompt)
        return n_train if entry.resume is None \
            else n_train + len(entry.resume[0]) - 1

    def _match_prefix(self, entry: _WaitEntry) -> list:
        """Radix-cache nodes whose pages this entry can attach: longest
        cached prefix of the prompt, quantized DOWN to a prefill-chunk
        boundary.  Chunk quantization is what makes cache hits bit-exact
        against a cold run: every remaining chunk then covers the same
        [t0, t0+nv) span the cold engine's schedule would, so the float
        reduction order of every summary-row sum and mixing output is
        identical.  Only fully window-aligned prompt prefixes are cached
        at all (see `_finish_prefill`), and at least one token is always
        left to prefill — the final chunk's logits seed sampling."""
        if self.cache is None:
            return []
        n_train = len(entry.req.prompt)
        if n_train % self.w:
            # only window-aligned prompts share summary rows: a prompt
            # whose length is not a multiple of the window trains its
            # summaries on a different (n//m-derived) grid, so cached
            # w-aligned rows would be wrong for it
            return []
        if self.ecfg.prefill_mode == "per-job" \
                and not self.backend.chunkable(n_train, batched=False):
            return []               # monolithic path packs from zero
        limit = min(n_train, self._entry_total(entry) - 1) // self.w
        if limit <= 0:
            return []
        nodes = self.cache.match(entry.req.prompt, limit)
        chunk_w = self.ecfg.prefill_chunk // self.w
        return nodes[: (len(nodes) // chunk_w) * chunk_w]

    def _first_chunk_pages(self, entry: _WaitEntry,
                           shared_pages: int = 0) -> int:
        """NEW pages the first prefill dispatch of this request needs
        beyond ``shared_pages`` attached from the prefix cache: one
        chunk's worth — or the whole (window-aligned) prompt when the
        backend's chunk program cannot start this prompt in per-job mode
        and it must go through the monolithic path."""
        n_train = len(entry.req.prompt)
        if self.ecfg.prefill_mode == "per-job" \
                and not self.backend.chunkable(n_train, batched=False):
            return self.backend.pages_needed(n_train)
        t0 = shared_pages * self.w
        first = min(self.ecfg.prefill_chunk, self._entry_total(entry) - t0)
        return self.backend.pages_needed(t0 + first) - shared_pages

    def _admit_chunked(self, now: float) -> list[int]:
        """Chunked admission: one request at a time, first-chunk pages only.
        A higher-priority arrival preempts the lowest strictly-lower victim
        when slots or pages run short (invariant 2 becomes priority-ordered
        head-of-line blocking).  With the prefix cache on, the prompt is
        matched against the radix tree first: matched pages attach by
        reference (one retained ref per page), the backend installs the
        cached per-window summary rows, and the prefill job starts at the
        first unshared chunk instead of zero."""
        admitted = []
        while self.waiting:
            entry = self.waiting[0]
            nodes = self._match_prefix(entry)
            first = self._first_chunk_pages(entry, len(nodes))
            if not self.free_slots or not self.alloc.can_alloc(first):
                self._preempt_for(entry.req.priority, first, need_slot=True)
                # pressure relief may have evicted matched cache nodes —
                # re-match before attaching anything
                nodes = self._match_prefix(entry)
                first = self._first_chunk_pages(entry, len(nodes))
                if not self.free_slots or not self.alloc.can_alloc(first):
                    break
            self.waiting.pop(0)
            admitted.append(entry.req.rid)
            slot = self.free_slots.pop()
            if entry.resume is None:
                toks = np.asarray(entry.req.prompt, np.int32)
            else:
                out = entry.resume[0]
                toks = np.concatenate([
                    np.asarray(entry.req.prompt, np.int32),
                    np.asarray(out[:-1], np.int32)])
            if entry.first_admit is None:
                entry.first_admit = now
            shared = len(nodes) * self.w
            self.prefilling[slot] = _PrefillJob(
                entry=entry, toks=toks, n_train=len(entry.req.prompt),
                admit_time=entry.first_admit, done=shared)
            self.backend.alloc_slot(slot)
            shared_pages = [nd.page for nd in nodes]
            if shared_pages:
                # attach by reference: the slot becomes one more holder of
                # each page; the cached summary rows make the backend's
                # state look exactly as if it had prefilled those windows
                self.alloc.retain(shared_pages)
                self.backend.attach_prefix(
                    slot, [nd.payload for nd in nodes])
                self.n_prefix_hits += 1
                self.n_pages_shared += len(shared_pages)
                self.n_prefix_tokens_reused += shared
                self.prefix_hits[entry.req.rid] = shared
            elif self.cache is not None:
                self.n_prefix_misses += 1
                self.prefix_hits.setdefault(entry.req.rid, 0)
            # claim the first dispatch's pages NOW so concurrent admissions
            # never overcommit the same free pages
            pages = shared_pages + self.alloc.alloc(first)
            self.slot_pages[slot] = pages
            self.page_table[slot] = 0
            self.page_table[slot, : len(pages)] = pages
            self.backend.invalidate()
            self._seq += 1
            self.slot_seq[slot] = self._seq
        return admitted

    def _admit_grouped(self, now: float) -> list[int]:
        """Monolithic admission (``prefill_chunk`` = 0): priority-then-FCFS
        with same-length grouping — the head-of-line request picks the
        prompt length; other waiting requests of that length ride along in
        ONE fused prefill+pack dispatch (prefill rows are independent, so
        grouping never changes a request's tokens).  Head-of-line blocking
        on pages is deliberate — big requests are not starved by later
        small ones.  The full page budget is claimed up front (invariant
        3), so this path never needs preemption."""
        admitted = []
        while self.waiting and self.free_slots:
            head = self.waiting[0].req
            if not self.alloc.can_alloc(self.pages_needed(head)):
                break
            n = len(head.prompt)
            budget = (len(self.alloc.free) - self.alloc.reserve
                      - self.pages_needed(head))
            group = [self.waiting[0]]
            for e in self.waiting[1:]:
                if len(group) >= len(self.free_slots):
                    break
                if len(e.req.prompt) == n and self.pages_needed(e.req) <= budget:
                    group.append(e)
                    budget -= self.pages_needed(e.req)
            # power-of-two chunks: bounds the (length, group-size) compile
            # variants to log2(slots) per prompt length (see `warmup`);
            # the remainder is admitted by the next loop iteration
            group = group[: 1 << (len(group).bit_length() - 1)]
            for e in group:
                self.waiting.remove(e)
            slots = [self.free_slots.pop() for _ in group]
            pages_list = [self.alloc.alloc(self.pages_needed(e.req))
                          for e in group]
            for slot in slots:
                self.backend.alloc_slot(slot)

            try:
                logits = self.backend.prefill_group(
                    np.stack([e.req.prompt for e in group]).astype(np.int32),
                    slots, pages_list)
            except Exception:
                # fault-atomic admission: at this point the group's pages
                # and slots are claimed but not yet recorded in slot_pages
                # / slot_req — a raising backend would leak them all.
                # Unwind to the pre-admission state (entries back in the
                # queue, pages freed, slots returned) and re-raise so the
                # supervisor can retry the whole step.
                for slot, pages in zip(slots, pages_list):
                    self.alloc.release(pages)
                    self.free_slots.append(slot)
                    self.backend.retire(slot)
                self.backend.invalidate()
                for e in group:
                    self._enqueue(e)
                raise

            admitted += [e.req.rid for e in group]
            for i, (entry, slot, pages) in enumerate(
                    zip(group, slots, pages_list)):
                req = entry.req
                self.slot_req[slot] = req
                self.slot_entry[slot] = entry
                self.slot_pages[slot] = pages
                self.slot_out[slot] = []
                self.slot_times[slot] = []
                self.slot_npre[slot] = 0
                self._seq += 1
                self.slot_seq[slot] = self._seq
                self.page_table[slot] = 0
                self.page_table[slot, : len(pages)] = pages
                self.t[slot] = n
                self.active[slot] = True
                self.slot_rid[slot] = req.rid
                self.slot_temp[slot] = req.temperature
                self.backend.slot_filled(slot, n)
                first = self._sample(logits[i], req, 0)
                self.sample_idx[slot] = 1
                self.slot_meta[slot] = (now, time.perf_counter())
                self._emit(slot, first, time.perf_counter())
                self.tokens_in[slot] = first
                if req.max_new_tokens == 1:
                    self._retire(slot, time.perf_counter())
            self.backend.invalidate()
        return admitted

    # ------------------------------------------------------ chunked prefill --

    def _grow_pages(self, slot: int, target: int) -> bool:
        """Grow ``slot`` to ``target`` pages for the next prefill dispatch.

        On pressure, pages flow toward the best-keyed admitted work: the
        globally worst occupant — lowest priority, then most recently
        admitted (FCFS within a class) — is evicted until the allocation
        fits.  The worst occupant is never better-keyed than this job (the
        job is itself a candidate), so higher-priority and more-senior work
        is never disturbed; if this job IS the pool's worst occupant while
        others wait on it, it yields (self-preempt).  The strict total
        order (priority, admission seq) is what rules out livelock between
        equal-priority jobs."""
        delta = target - len(self.slot_pages[slot])
        if delta <= 0:
            return True
        self._reclaim_cache(delta)
        while not self.alloc.can_alloc(delta):
            victim = self._pick_victim()
            if victim is None or victim == slot:
                break
            self._preempt(victim)
            self._reclaim_cache(delta)
        if not self.alloc.can_alloc(delta):
            occupied = len(self.prefilling) + len(self.slot_req)
            if occupied > 1 and self._pick_victim() == slot:
                self._preempt(slot)
            return False
        pages = self.alloc.alloc(delta)
        base = len(self.slot_pages[slot])
        for i, p in enumerate(pages):
            self.page_table[slot, base + i] = p
        self.slot_pages[slot].extend(pages)
        self.backend.invalidate()
        return True

    def _advance_prefill(self, now: float) -> None:
        """Advance prefilling jobs: ONE fused dispatch per engine step.

        Batched mode (default): every prefilling slot that can grow its
        pages advances one chunk in a single `prefill_chunks` dispatch
        over a slot mask.  Per-job mode (the legacy baseline): only the
        best-keyed job advances, in its own dispatch."""
        if not self.prefilling:
            return
        with spans.span("engine.prefill", step=self.steps):
            if self.ecfg.prefill_mode == "batched":
                self._advance_prefill_batched(now)
            else:
                self._advance_prefill_per_job(now)

    def _advance_prefill_batched(self, now: float) -> None:
        """One dispatch advances EVERY prefilling job one chunk.  Jobs that
        cannot claim their next pages are masked out of the dispatch (and
        may have been self-preempted by `_grow_pages`), not serialized.
        Page growth runs best-key-first, so the victim order of `_grow
        _pages` (globally worst key first) can never evict a job already
        approved this step."""
        chunk = self.ecfg.prefill_chunk
        advancing: list[tuple[int, _PrefillJob, int]] = []
        for slot, job in sorted(self.prefilling.items(),
                                key=lambda kv: kv[1].entry.key):
            if self.prefilling.get(slot) is not job:
                continue              # evicted while an earlier job grew
            t0 = job.done
            nv = min(chunk, len(job.toks) - t0)
            target = self.backend.pages_needed(t0 + nv)
            if not self._grow_pages(slot, target):
                continue
            if self.prefilling.get(slot) is job:
                advancing.append((slot, job, nv))
        if not advancing:
            return
        # rows are jobs, packed to a power-of-two width so compute scales
        # with the number of prefilling requests (log2(slots)+1 compiled
        # variants — the monolithic admission-grouping bound).  Padding
        # rows borrow DISTINCT idle slot ids (inactive rows write their
        # slot's state back bit-identically), so the state scatter never
        # sees duplicate indices.
        p_w = 1 << (len(advancing) - 1).bit_length() if advancing else 1
        p_w = min(p_w, self.ecfg.n_slots)
        used = {s for s, _, _ in advancing}
        pads = [s for s in range(self.ecfg.n_slots) if s not in used]
        slot_ids = [s for s, _, _ in advancing] + pads[: p_w - len(advancing)]
        toks = np.zeros((p_w, chunk), np.int32)
        job_active = np.zeros(p_w, bool)
        t0s = np.zeros(p_w, np.int32)
        nvs = np.zeros(p_w, np.int32)
        ntr = np.ones(p_w, np.int32)
        for i, (slot, job, nv) in enumerate(advancing):
            toks[i, :nv] = job.toks[job.done:job.done + nv]
            job_active[i] = True
            t0s[i] = job.done
            nvs[i] = nv
            ntr[i] = job.n_train
        logits = self.backend.prefill_chunks(
            slot_ids, toks, job_active, self.page_table[slot_ids],
            t0s, nvs, ntr)
        self.n_chunks += len(advancing)
        self.prefill_dispatches += 1
        for i, (slot, job, nv) in enumerate(advancing):
            job.done += nv
            if job.done == len(job.toks):
                self._finish_prefill(slot, job, logits[i], now)

    def _advance_prefill_per_job(self, now: float) -> None:
        """Run ONE prefill dispatch (a chunk, or the monolithic path for a
        prompt the chunk program cannot start) for the best prefilling job
        — bounding per-step added latency to one chunk regardless of
        prompt length."""
        slot, job = min(self.prefilling.items(),
                        key=lambda kv: kv[1].entry.key)
        n_total = len(job.toks)
        if job.done == 0 and not self.backend.chunkable(job.n_train,
                                                        batched=False):
            # monolithic path: the program this prompt length would have
            # used unchunked (see docs/serving.md)
            n = job.n_train
            if not self._grow_pages(slot, self.backend.pages_needed(n)):
                return
            logits = self.backend.prefill_group(
                job.toks[None, :n].astype(np.int32), [slot],
                [self.slot_pages[slot]])
            job.done = n
            self.prefill_dispatches += 1
            if job.done == n_total:
                self._finish_prefill(slot, job, logits[0], now)
            return
        chunk = self.ecfg.prefill_chunk
        t0 = job.done
        nv = min(chunk, n_total - t0)
        if not self._grow_pages(slot, self.backend.pages_needed(t0 + nv)):
            return
        toks = np.zeros(chunk, np.int32)
        toks[:nv] = job.toks[t0:t0 + nv]
        logits = self.backend.prefill_chunk(
            slot, self.page_table[slot], toks, t0, nv, job.n_train)
        self.n_chunks += 1
        self.prefill_dispatches += 1
        job.done = t0 + nv
        if job.done == n_total:
            self._finish_prefill(slot, job, logits, now)

    def _finish_prefill(self, slot: int, job: _PrefillJob,
                        logits: np.ndarray, now: float) -> None:
        """Last chunk landed: move the slot into the decode batch.  Fresh
        requests sample their first token from the final chunk's logits;
        resumed (preempted) requests restore their emitted tokens and
        continue decoding from where they were evicted."""
        entry = job.entry
        req = entry.req
        del self.prefilling[slot]
        n_total = len(job.toks)
        self.slot_req[slot] = req
        self.slot_entry[slot] = entry
        self.t[slot] = n_total
        self.active[slot] = True
        self.backend.slot_filled(slot, n_total, snapshot=entry.snapshot)
        entry.snapshot = None
        self.backend.invalidate()
        if self.cache is not None and job.n_train % self.w == 0:
            # commit this prompt's windows to the radix cache: each new
            # node retains one reference on its page; the snapshot of the
            # per-window summary rows is taken lazily (only if the walk
            # actually adds nodes).  Shared-then-extended prompts deepen
            # an existing path; physically-diverging duplicates add
            # nothing (a node's rows must only reference pages on its own
            # root-anchored path)
            m = job.n_train // self.w
            self.cache.insert(
                job.toks, m, self.slot_pages[slot][:m],
                lambda: self.backend.prefix_snapshot(slot, m))
        self.slot_npre[slot] = entry.evictions
        self.slot_rid[slot] = req.rid
        self.slot_temp[slot] = req.temperature
        if entry.resume is None:
            self.slot_out[slot] = []
            self.slot_times[slot] = []
            first = self._sample(logits, req, 0)
            self.sample_idx[slot] = 1
            self.slot_meta[slot] = (job.admit_time, time.perf_counter())
            self._emit(slot, first, time.perf_counter())
            self.tokens_in[slot] = first
            if req.max_new_tokens == 1:
                self._retire(slot, time.perf_counter())
        else:
            out, times, meta = entry.resume
            entry.resume = None
            self.slot_out[slot] = list(out)
            self.slot_times[slot] = list(times)
            self.slot_meta[slot] = meta
            self.sample_idx[slot] = len(out)
            self.tokens_in[slot] = out[-1]

    def _ensure_append_pages(self) -> None:
        """Guarantee every active slot owns the page its next append lands
        in (invariant 3 in incremental form).  Appends may dip into the
        reserve; if the pool is truly dry the lowest-priority slot is
        preempted — possibly the appender itself, whose pages then fund the
        survivors."""
        for slot in np.nonzero(self.active)[0]:
            slot = int(slot)
            # one speculative round can commit up to spec_k + 1 tokens, so
            # a slot's position may have crossed SEVERAL page boundaries
            # since the last pass — grow page by page until covered
            # (non-speculative decode advances by one token and takes at
            # most one iteration, exactly the old behavior)
            while (self.active[slot]
                   and int(self.t[slot]) // self.w
                   >= len(self.slot_pages[slot])):
                need_idx = len(self.slot_pages[slot])
                self._reclaim_cache(1, reserved=True)
                while not self.alloc.can_alloc(1, reserved=True):
                    victim = self._pick_victim()
                    if victim is None:
                        break
                    self._preempt(victim)
                    self._reclaim_cache(1, reserved=True)
                    if victim == slot:
                        break
                if not self.active[slot]:
                    break             # preempted as a victim this pass
                page = self.alloc.alloc(1, reserved=True)[0]
                # a decode append writes the page in place (the fused
                # step's aliased scatter), so its target must never be
                # shared: fresh allocations carry exactly one reference,
                # and append pages are never inserted into the prefix
                # cache (inserts cover prompt windows only, which precede
                # every append index)
                assert self.alloc.refcount(page) == 1
                self.slot_pages[slot].append(page)
                self.page_table[slot, need_idx] = page
                self.backend.invalidate()

    # ---------------------------------------------------- speculative round --

    def _spec_round(self, now: float) -> None:
        """One draft/verify/commit round for the whole active batch.

        Per-slot draft length = min(spec_k, remaining - 1, the backend's
        draft horizon), floored at 0 — a zero-length slot still runs verify
        position 0 and commits one token, so every request retires at
        exactly the step count the non-speculative engine would reach.
        The commit rule is the lossless one: keep the longest draft prefix
        the exact decode rule reproduced token-for-token, plus its first
        correction; rejected suffix state is rewound by the backend."""
        k = self.ecfg.spec_k
        act = [int(s) for s in np.nonzero(self.active)[0]]
        remaining = np.zeros_like(self.t)
        for slot in act:
            remaining[slot] = (self.slot_req[slot].max_new_tokens
                               - len(self.slot_out[slot]))
        horizon = np.asarray(self.backend.draft_horizon(self.t))
        spec_len = np.where(
            self.active,
            np.minimum(np.minimum(k, remaining - 1), horizon),
            0).astype(np.int32)
        spec_len = np.maximum(spec_len, 0)

        drafts = self.backend.draft_steps(
            self.tokens_in, self.t, self.active, self.page_table,
            self.slot_rid, self.slot_temp, self.sample_idx, self._key,
            spec_len)
        verify = self.backend.verify_step(
            self.tokens_in, self.t, self.active, self.page_table,
            self.slot_rid, self.slot_temp, self.sample_idx, self._key,
            spec_len, drafts)

        commits = np.ones(len(self.t), np.int32)
        for slot in act:
            sl = int(spec_len[slot])
            j = 0
            while j < sl and drafts[j, slot] == verify[j, slot]:
                j += 1
            commits[slot] = j + 1
            self.n_spec_drafted += sl
            self.n_spec_accepted += j
            self.n_spec_rollbacks += int(j < sl)
        self.backend.rollback(commits, self.active)

        for slot in act:
            req = self.slot_req[slot]
            c = int(commits[slot])
            for i in range(c):
                self._emit(slot, int(verify[i, slot]), now)
            self.t[slot] += c
            self.sample_idx[slot] += c
            self.tokens_in[slot] = int(verify[c - 1, slot])
            if len(self.slot_out[slot]) >= req.max_new_tokens:
                self._retire(slot, now)
        # scheduler tensors moved by per-slot amounts: device mirrors are
        # stale no matter what (retire already invalidates, but a round
        # with no retirement must too)
        self.backend.invalidate()

    # ---------------------------------------------------------------- step --

    def step(self) -> bool:
        """One engine iteration: retire/admit, advance at most one prefill
        chunk, then one fused decode step — or, with ``spec_k`` > 0, one
        speculative draft/verify/commit round — for the active batch.
        Returns False when there is nothing left to do.  Each phase is a
        host span (`serve.spans`) under the step's ``engine.step``."""
        step = self.steps
        with spans.step_span(step):
            with spans.span("engine.admit", step=step) as sp:
                self._expire_deadlines()
                now = time.perf_counter()
                admitted = self._admit(now)
                if admitted and sp.is_enabled():
                    sp.set_metadata(rid=" ".join(map(str, admitted)))
            self._advance_prefill(now)
            if self.ecfg.prefill_chunk:
                with spans.span("engine.pages", step=step):
                    self._ensure_append_pages()
            if not self.active.any():
                return bool(self.waiting or self.prefilling)

            if self.ecfg.spec_k:
                t0 = time.perf_counter()
                with spans.span("engine.spec", step=step):
                    self._spec_round(time.perf_counter())
                self.step_times.append(time.perf_counter() - t0)
                self.steps += 1
                return True

            fused_sampling = self.ecfg.sample_device == "fused"
            t0 = time.perf_counter()
            # fused sampling downloads [S] int32 tokens; the host path the
            # whole [S, V] logits (docs/serving.md, host-transfer budget)
            out = self.backend.decode_step(
                self.tokens_in, self.t, self.active, self.page_table,
                self.slot_rid, self.slot_temp, self.sample_idx, self._key)
            self.step_times.append(time.perf_counter() - t0)
            self.steps += 1

            now = time.perf_counter()
            with spans.span("engine.emit", step=step):
                for slot in np.nonzero(self.active)[0]:
                    req = self.slot_req[slot]
                    if fused_sampling:
                        tok = int(out[slot])
                    else:
                        tok = self._sample(out[slot], req,
                                           len(self.slot_out[slot]))
                    self._emit(slot, tok, now)
                    self.t[slot] += 1
                    self.sample_idx[slot] += 1
                    self.tokens_in[slot] = tok
                    if len(self.slot_out[slot]) >= req.max_new_tokens:
                        self._retire(slot, now)
            return True

    def run(self, requests: list[Request],
            realtime: bool = False) -> list[FinishedRequest]:
        """Drive a whole trace, returning the requests finished during THIS
        call (an engine can serve many traces back-to-back).
        ``realtime=True`` honours arrival offsets on the wall clock
        (Poisson traces); otherwise all requests queue up front
        (max-throughput mode)."""
        pending = sorted(requests, key=lambda r: r.arrival)
        start = time.perf_counter()
        already_done = len(self.finished)
        idx = 0
        while (idx < len(pending) or self.waiting or self.prefilling
               or self.active.any()):
            now = time.perf_counter() - start
            while idx < len(pending) and (
                    not realtime or pending[idx].arrival <= now):
                self.submit(pending[idx])
                idx += 1
            progressed = self.step()
            if not progressed and idx < len(pending):
                if realtime:
                    time.sleep(max(0.0,
                                   pending[idx].arrival
                                   - (time.perf_counter() - start)))
        return sorted(self.finished[already_done:], key=lambda f: f.rid)
