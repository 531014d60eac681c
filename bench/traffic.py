"""The one traffic generator: a traffic file of parameters -> requests.

A traffic file (``bench/traffic/<name>.json``) holds only numbers and
choices; this module reads every one of them.  Keys:

  loop         "open" (requests due on a Poisson schedule, ``rate_per_s``)
               or "closed" (one client per slot; a client sends its next
               request when its last one finishes)
  prompt       {"min", "max", "multiple"}: prompt lengths, log-uniform,
               rounded down to a whole number of ``multiple`` tokens
  output       {"min", "max", "multiple"}: generated tokens, the same way
  block        lengths (and open-loop gaps) are drawn stratified in blocks
               of this many requests: every block holds the same spread of
               sizes
  warm_s       seconds of traffic before the measured window (open loop),
               or after every slot first holds a request (closed loop)
  stagger      closed loop: a client's first request generates a share
               of its drawn length, so slots start at staggered progress
               and finish at staggered times
  check        {"tokens": served tokens to compare, at least; "requests":
               at most this many requests} — the sample the reference
               checks

The sizes, gaps and staggers are one sequence per traffic file, the same
for every seed: a window holds only some tens of requests, and when the
seed reordered them, which requests fell in the window moved the cell's
throughput by more than a run's own noise.  The seed draws the prompts'
token ids (a Markov-Zipf stream over the vocabulary) and the weights.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np


@dataclasses.dataclass(frozen=True)
class Draw:
    """One request as the generator makes it."""
    prompt: np.ndarray      # [n] int32
    max_new: int


def markov_zipf(rng: np.random.Generator, n: int, vocab: int) -> np.ndarray:
    """A structured token stream: Zipf-distributed jumps on a drifting
    base, so ids repeat and cluster as text does."""
    base = rng.zipf(1.5, size=n).astype(np.int64)
    drift = np.cumsum(rng.integers(0, 7, size=n))
    return ((base + drift) % vocab).astype(np.int32)


def _stratified(rng: np.random.Generator, n: int) -> np.ndarray:
    """n uniforms, one in each of n equal strata, in a shuffled order."""
    return (rng.permutation(n) + rng.random(n)) / n


def _loguniform(u: np.ndarray, spec: dict) -> np.ndarray:
    lo, hi = math.log(spec["min"]), math.log(spec["max"])
    mult = int(spec.get("multiple", 1))
    x = np.exp(lo + u * (hi - lo))
    x = np.floor(x / mult) * mult
    return np.clip(x, spec["min"], spec["max"]).astype(np.int64)


class Traffic:
    """Deterministic in (traffic file, seed, vocabulary)."""

    def __init__(self, spec: dict, seed: int, vocab: int):
        if spec["loop"] not in ("open", "closed"):
            raise ValueError(f"unknown loop {spec['loop']!r}")
        self.spec = spec
        self.seed = int(seed)
        self.vocab = vocab
        self.block = int(spec.get("block", 32))
        self._blocks: dict[int, tuple] = {}

    # ------------------------------------------------------------ draws --

    def _rng(self, *path: int) -> np.random.Generator:
        return np.random.default_rng([self.seed, *path])

    def _block(self, b: int):
        if b not in self._blocks:
            rng = np.random.default_rng([0, b])
            n = self.block
            self._blocks[b] = (
                _loguniform(_stratified(rng, n), self.spec["prompt"]),
                _loguniform(_stratified(rng, n), self.spec["output"]),
                -np.log1p(-_stratified(rng, n)))
        return self._blocks[b]

    def draw(self, i: int) -> Draw:
        """The i-th request of the stream (open loop: in due order;
        closed loop: see `client_draw`)."""
        prompt_n, out_n, _ = self._block(i // self.block)
        j = i % self.block
        toks = markov_zipf(self._rng(1, i), int(prompt_n[j]), self.vocab)
        return Draw(prompt=toks, max_new=int(out_n[j]))

    # -------------------------------------------------------- open loop --

    def dues(self, n: int) -> np.ndarray:
        """Seconds from the start of traffic at which each of the first n
        requests is due (open loop): Poisson gaps at ``rate_per_s``."""
        rate = float(self.spec["rate_per_s"])
        gaps = np.concatenate([self._block(b)[2]
                               for b in range(-(-n // self.block))])[:n]
        return np.cumsum(gaps) / rate

    # ------------------------------------------------------ closed loop --

    def client_draw(self, client: int, k: int, clients: int) -> Draw:
        """Client ``client``'s k-th request: the k-th requests of all
        clients together draw from consecutive strata.  With ``stagger``
        the first one generates a share of its length, each client's
        different."""
        d = self.draw(k * clients + client)
        if k == 0 and self.spec.get("stagger"):
            share = np.random.default_rng(2).permutation(clients)[client]
            frac = (share + 0.5) / clients
            d = Draw(prompt=d.prompt, max_new=max(1, int(d.max_new * frac)))
        return d

    # --------------------------------------------------------- sizing ----

    def max_context(self) -> int:
        return int(self.spec["prompt"]["max"] + self.spec["output"]["max"])
