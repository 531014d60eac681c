"""The comparison that decides ``correct``: served tokens against the
plain reference.

Once the window has closed, a sample of the requests the run finished
after the window opened is drawn from the seed, with the longest of them
in it, until it holds the traffic file's number of served tokens.  The
reference runs once over each prompt and its served tokens (teacher
forced).  At each served token the gap is how far the token's reference
logit lies below the reference's best logit at that position.  The
numbers compared are those the cell's file limits (`NUMBERS`): the mean
or the median gap over every compared token.

Not the widest gap: MiTA routes each query to one landmark by an argmax
and keeps each expert's keys by a top-k.  In a model with random weights
the landmark queries (means of 128 unrelated queries) are small and
alike, so routing decisions are near ties, and rounding that flips one
changes what a head attends to.  A bfloat16 program and the float32
reference part at a share of positions by far more than rounding, and the
widest gap over hundreds of tokens reads about as high for a correct
program as for the float8 control (PERF.md).  The mean is steadier: most
served tokens of a correct program are the reference's first choice.
Where long contexts make near ties common, the flipped positions lift
the program's mean towards the control's, and the median, which reads
the typical token, still tells them apart.

The control puts the reference in the program's place at float8 (e4m3)
operands: at each position it reads the gap of the token that the float8
reference puts first.
"""

from __future__ import annotations

import importlib.util
import math
from pathlib import Path

import numpy as np

CONFIGS = Path(__file__).resolve().parent / "configs"


def reference(spec: dict, configs_dir: Path = CONFIGS):
    """The plain reference module that a configuration file names."""
    name = spec["reference"]
    mspec = importlib.util.spec_from_file_location(
        f"bench_reference_{name}", configs_dir / f"{name}.py")
    mod = importlib.util.module_from_spec(mspec)
    mspec.loader.exec_module(mod)
    return mod


def sample(recs, w0: float, seed: int, min_tokens: int,
           max_requests: int) -> list:
    """Completed requests that finished after ``w0``: the longest (prompt
    and output), the one with the most output, then others in a
    seed-drawn order, until ``min_tokens`` served tokens or
    ``max_requests`` requests."""
    done = [r for r in recs.values()
            if r.reason == "complete" and r.finished >= w0
            and r.tokens is not None and len(r.tokens)]
    if not done:
        return []
    done.sort(key=lambda r: r.rid)
    order = [max(done, key=lambda r: len(r.prompt) + len(r.tokens)),
             max(done, key=lambda r: len(r.tokens))]
    rng = np.random.default_rng([int(seed), 7])
    order += [done[i] for i in rng.permutation(len(done))]
    out, seen, n = [], set(), 0
    for r in order:
        if r.rid in seen:
            continue
        out.append(r)
        seen.add(r.rid)
        n += len(r.tokens)
        if n >= min_tokens or len(out) >= max_requests:
            break
    return out


def gaps(ref_mod, params, spec, rec, pad: int, rows: int,
         control: bool = False):
    """Per served token: the reference's best logit less the served
    token's, and (with ``control``) less the float8 reference's first
    choice's.  ``pad``, ``rows``: the cell's longest sequence and
    output, which every request is padded to, so the reference compiles
    once per run."""
    import jax.numpy as jnp

    seq = np.concatenate([rec.prompt, rec.tokens[:-1]]).astype(np.int32)
    n = len(rec.prompt)
    ref = ref_mod.logits(params, spec, seq, n, pad_to=pad, rows=rows)
    best = jnp.max(ref, axis=-1)
    served = jnp.take_along_axis(ref, jnp.asarray(rec.tokens)[:, None],
                                 axis=-1)[:, 0]
    prog = np.asarray(best - served)
    if not control:
        return prog, None
    low = ref_mod.logits(params, spec, seq, n, low=True, pad_to=pad,
                         rows=rows)
    pick = jnp.argmax(low, axis=-1)
    ctrl = np.asarray(best - jnp.take_along_axis(ref, pick[:, None],
                                                 axis=-1)[:, 0])
    return prog, ctrl


def describe(rec, prog, ctrl, seconds: float) -> dict:
    """One compared request: sizes, where its widest gap lies (served
    token index), how many tokens lie off the reference's best, and the
    control's widest gap."""
    out = {"prompt": len(rec.prompt), "served": len(rec.tokens),
           "gap_mean": float(np.mean(prog)),
           "gap_median": float(np.median(prog)),
           "gap": float(np.max(prog)), "at": int(np.argmax(prog)),
           "off_best": int(np.sum(prog > 0)), "seconds": round(seconds, 2)}
    if ctrl is not None:
        out["control_gap_mean"] = float(np.mean(ctrl))
        out["control_gap_median"] = float(np.median(ctrl))
        out["control_gap"] = float(np.max(ctrl))
        out["control_off_best"] = int(np.sum(ctrl > 0))
    return out


def _flat(values):
    """Every compared token's gap, or None where there is no token or a
    NaN (a reference that gave no number): that reads as infinitely
    wide."""
    flat = np.concatenate([np.asarray(v, np.float64).ravel()
                           for v in values]) if values else np.zeros(0)
    if not flat.size or np.isnan(flat).any():
        return None
    return flat


def mean_gap(values) -> float:
    """The mean gap over every compared token."""
    flat = _flat(values)
    return math.inf if flat is None else float(flat.mean())


def median_gap(values) -> float:
    """The median gap over every compared token."""
    flat = _flat(values)
    return math.inf if flat is None else float(np.median(flat))


# the numbers a cell's ``limits`` (bench/cells/<cell>.json) can name
NUMBERS = {"logit_gap_mean": mean_gap, "logit_gap_median": median_gap}
