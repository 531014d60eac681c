"""The load generator: the benchmark's own copy of the supervisor's drive
loop, with the stamps and host spans the metrics read.

It submits each request at its due time (open loop) or when its client's
last request finished (closed loop), steps the supervised engine while it
has work, and waits for the next due time when it has none.  Every request
is stamped with its due and submit times here; admission, first-token and
token times are the engine's own stamps, on the same clock
(`time.perf_counter`).

Host spans (`jax.profiler.TraceAnnotation`), which a traced run finds in
the profiler's trace beside the device's operations:

  bench.submit   handing due requests to the engine
  bench.step     one supervised engine step
  bench.decode   the backend's fused decode dispatch, inside a step
  bench.prefill  the backend's batched chunk-prefill dispatch, inside one
  bench.wait     sleeping until the next request is due
"""

from __future__ import annotations

import dataclasses
import math
import time
from typing import Callable, Optional

import numpy as np
from jax.profiler import TraceAnnotation

CLOCK = time.perf_counter

SPANS = ("bench.submit", "bench.step", "bench.decode", "bench.prefill",
         "bench.wait")


@dataclasses.dataclass
class Rec:
    """One request as the load generator sent it, and what came back."""
    rid: int
    client: int
    due: float
    prompt: np.ndarray
    max_new: int
    submit: float = math.nan
    admitted: float = math.nan
    first_token: float = math.nan
    finished: float = math.nan
    token_times: list = dataclasses.field(default_factory=list)
    tokens: Optional[np.ndarray] = None
    reason: str = ""


@dataclasses.dataclass
class Dispatch:
    """One backend dispatch, timed on the host around the call (which
    ends in a blocking download of its result)."""
    kind: str               # "decode" | "prefill"
    t0: float
    t1: float
    positions: np.ndarray   # decode: each active slot's position
    rows: np.ndarray        # prefill: [rows, 2] (resume point, tokens)


class Driver:
    def __init__(self, sup, traffic, slots: int):
        self.sup = sup
        self.eng = sup.engine
        self.traffic = traffic
        self.slots = slots
        self.open = traffic.spec["loop"] == "open"
        self.recs: dict[int, Rec] = {}
        self.dispatches: list[Dispatch] = []
        self.busy: list[tuple[float, float]] = []  # spans with work
        self._next_rid = 0
        self._n_finished = 0
        self._client_k = [0] * slots
        self._ready: list[tuple[int, float]] = []  # closed: (client, due)
        self._wrap_backend()

    # ---------------------------------------------------------- record --

    def _wrap_backend(self) -> None:
        be = self.eng.backend
        decode, prefill = be.decode_step, be.prefill_chunks

        def decode_step(tokens_in, t, active, *a, **k):
            t0 = CLOCK()
            with TraceAnnotation("bench.decode"):
                out = decode(tokens_in, t, active, *a, **k)
            self.dispatches.append(Dispatch(
                "decode", t0, CLOCK(), np.asarray(t)[np.asarray(active)],
                np.zeros((0, 2), np.int64)))
            return out

        def prefill_chunks(slot_ids, toks, job_active, page_table, t0s,
                           n_valid, n_train):
            t0 = CLOCK()
            with TraceAnnotation("bench.prefill"):
                out = prefill(slot_ids, toks, job_active, page_table, t0s,
                              n_valid, n_train)
            act = np.asarray(job_active)
            rows = np.stack([np.asarray(t0s)[act],
                             np.asarray(n_valid)[act]], axis=1)
            self.dispatches.append(Dispatch(
                "prefill", t0, CLOCK(), np.zeros(0, np.int64), rows))
            return out

        be.decode_step = decode_step
        be.prefill_chunks = prefill_chunks

    # ------------------------------------------------------------ load --

    def start(self, horizon_s: float) -> None:
        """Start the traffic clock.  ``horizon_s``: the open loop's
        schedule is laid out this far ahead."""
        self.t_start = CLOCK()
        if self.open:
            rate = float(self.traffic.spec["rate_per_s"])
            n = int(rate * horizon_s * 1.5) + 64
            self.dues = self.t_start + self.traffic.dues(n)
            self._idx = 0
        else:
            self._ready = [(c, self.t_start) for c in range(self.slots)]

    def _send(self, client: int, due: float, draw) -> None:
        rid = self._next_rid
        self._next_rid += 1
        rec = Rec(rid=rid, client=client, due=due, prompt=draw.prompt,
                  max_new=draw.max_new)
        self.recs[rid] = rec
        from repro.serve import Request
        rec.submit = CLOCK()
        self.sup.submit(Request(rid=rid, prompt=draw.prompt,
                                max_new_tokens=draw.max_new))

    def _submit_due(self, now: float) -> None:
        if self.open:
            if self._idx >= len(self.dues) or self.dues[self._idx] > now:
                return
            with TraceAnnotation("bench.submit"):
                while self._idx < len(self.dues) and \
                        self.dues[self._idx] <= now:
                    self._send(-1, float(self.dues[self._idx]),
                               self.traffic.draw(self._idx))
                    self._idx += 1
            return
        if not self._ready:
            return
        with TraceAnnotation("bench.submit"):
            for client, due in self._ready:
                k = self._client_k[client]
                self._client_k[client] += 1
                self._send(client, due, self.traffic.client_draw(
                    client, k, self.slots))
        self._ready = []

    def _collect(self) -> None:
        done = self.eng.finished
        for f in done[self._n_finished:]:
            rec = self.recs.get(f.rid)
            if rec is None:
                continue            # a warm-up probe
            rec.admitted, rec.first_token = f.admitted, f.first_token
            rec.finished, rec.reason = f.finished, f.reason
            rec.token_times = list(f.token_times)
            rec.tokens = np.asarray(f.tokens)
            if f.reason == "rejected":
                rec.admitted = rec.first_token = math.nan
            if not self.open:
                self._ready.append((rec.client, f.finished))
        self._n_finished = len(done)

    def has_work(self) -> bool:
        e = self.eng
        return bool(e.waiting or e.prefilling or e.active.any())

    def iterate(self) -> None:
        """One pass of the drive loop."""
        now = CLOCK()
        self._submit_due(now)
        if self.has_work():
            with TraceAnnotation("bench.step"):
                self.sup.step()
            self.busy.append((now, CLOCK()))
        else:
            nxt = (self.dues[self._idx] if self.open
                   and self._idx < len(self.dues) else now + 1e-3)
            with TraceAnnotation("bench.wait"):
                time.sleep(max(0.0, min(nxt - CLOCK(), 0.05)))
        self._collect()

    def run_until(self, stop: Callable[[], bool]) -> None:
        while not stop():
            self.iterate()

    # -------------------------------------------------------- in flight --

    def settle(self) -> None:
        """Copy the stamps of requests still in flight into their records
        (admitted, first token, tokens so far)."""
        e = self.eng
        for slot, req in e.slot_req.items():
            rec = self.recs.get(req.rid)
            if rec is None:
                continue
            rec.admitted, rec.first_token = e.slot_meta[slot]
            rec.token_times = list(e.slot_times[slot])
            rec.tokens = np.asarray(e.slot_out[slot], np.int32)
        for job in e.prefilling.values():
            rec = self.recs.get(job.entry.req.rid)
            if rec is not None:
                rec.admitted = job.admit_time
        for entry in e.waiting:
            rec = self.recs.get(entry.req.rid)
            if rec is not None and entry.resume is not None:
                out, times, meta = entry.resume
                rec.admitted, rec.first_token = meta
                rec.token_times = list(times)
                rec.tokens = np.asarray(out, np.int32)
            elif rec is not None and entry.first_admit is not None:
                rec.admitted = entry.first_admit
