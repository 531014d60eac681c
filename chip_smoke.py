#!/usr/bin/env python3
"""Chip smoke test: the full-width qwen3-0.6b serving path on a TPU.

    python3 chip_smoke.py            # one chip
    python3 chip_smoke.py --chips 4  # four chips: the sharded trainer only

One chip, in one process:

  1. **kernels** — each serving kernel (paged decode, batched chunk
     prefill, paged finalize) against its XLA oracle in
     `repro.core.mita_decode`, at qwen3-0.6b widths (8 KV heads, 2 query
     heads per group, head dim 128, window 128, expert width 128) on a
     random paged state made from a seed;
  2. **serve** — `repro.launch.serve` (the CLI's own `run`) with the
     continuous engine, 512-token batched chunked prefill and fused
     sampling: 16 requests of 1536 prompt tokens on 4 slots, random
     weights from the seed.  Every request must complete with all three
     kernel-fallback counters, ``retries`` and ``degradation_level`` at 0.

``--chips 4`` runs only a few sharded train steps of qwen3-0.6b (full
width, depth cut to 4 layers) on a 2x2 ("data", "model") mesh and the same
steps on one device; the losses must agree and the sharded step must
spread its state over all four chips.

Exits nonzero, printing no result line, when JAX finds no TPU, when the
repository's sources are not next to this script, or when any check
fails.  On success the last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

ARCH = "qwen3-0.6b"
SEED = 0

# Kernel-vs-oracle tolerances.  Both sides read the same bf16-valued
# state (f32 pools hold bf16-rounded K/V; landmark tiles and queries are
# bf16), so scores agree up to f32 accumulation order.  What differs is
# where each side rounds an f32 operand to bf16 on the MXU — softmax
# weights, the 1/sqrt(d)-scaled query — a relative error of at most
# 2^-8 per rounding.  Outputs are convex combinations of unit-scale
# values, so a few such roundings stay well inside OUT_ATOL; landmark
# queries are the same bf16 rounding of the same f32 sum on both sides.
OUT_ATOL = 3e-2          # attention outputs, landmark values
LMQ_ATOL = 1e-2          # landmark queries (bf16: 2^-8 relative, |q| < 2)
# top-k expert selection over ~1.6k context positions may swap a near-tie
# at the K-th place when scores differ in the last bits
EXPERT_OVERLAP_MIN = 0.98
# sharded vs single-device train loss (bf16 matmuls, reduction order)
LOSS_ATOL = 2e-2


class SmokeFailure(Exception):
    """A phase's check failed."""


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


# ------------------------------------------------------------ kernels ----

def _page_table(n_slots, m_slot):
    """A shuffled page table over ``n_slots * m_slot + 2`` pages."""
    import numpy as np
    n_pages = n_slots * m_slot + 2
    table = np.random.default_rng(SEED).permutation(n_pages)
    return table[: n_slots * m_slot].reshape(n_slots, m_slot).astype(
        np.int32)


def _random_state(key, cfg, n_slots, m_slot, hkv, d, t_fill):
    """A paged MiTA state whose pools, landmarks and expert rows look like
    a served one: bf16-valued K/V in f32 pools over a shuffled page table,
    expert rows = distinct context positions < each slot's fill level
    mapped to GLOBAL pool rows."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import mita_decode as mdec

    w, k_w = cfg.window, cfg.k
    n_pages = n_slots * m_slot + 2
    rng = np.random.default_rng(SEED + 1)
    pt = _page_table(n_slots, m_slot)
    st = mdec.init_paged_state(hkv, d, n_pages, n_slots, m_slot, cfg,
                               jnp.bfloat16)
    ks = jax.random.split(key, 5)
    bf = lambda k, s: jax.random.normal(k, s).astype(jnp.bfloat16)
    loc = np.zeros((n_slots, hkv, m_slot, k_w), np.int64)
    for s in range(n_slots):
        fill = min(max(int(t_fill[s]), k_w), m_slot * w)
        for h in range(hkv):
            for m in range(m_slot):
                loc[s, h, m] = rng.permutation(fill)[:k_w]
    rows = pt[np.arange(n_slots)[:, None, None, None], loc // w] * w + loc % w
    st = st._replace(
        k_pool=bf(ks[0], st.k_pool.shape).astype(jnp.float32),
        v_pool=bf(ks[1], st.v_pool.shape).astype(jnp.float32),
        lm_q=bf(ks[2], st.lm_q.shape), lm_v=bf(ks[3], st.lm_v.shape),
        expert_idx=jnp.asarray(rows, jnp.int32),
        expert_valid=jnp.asarray(rng.random(loc.shape) < 0.95),
        q_sum=jax.random.normal(ks[4], st.q_sum.shape) * 8.0)
    return st, jnp.asarray(pt)


def _expert_overlap(a, b):
    """Mean fraction of shared expert rows per (slot, head, landmark)
    (an unwritten landmark's all-zero row counts as one shared row)."""
    import numpy as np
    a, b = np.asarray(a), np.asarray(b)
    flat_a = a.reshape(-1, a.shape[-1])
    flat_b = b.reshape(-1, b.shape[-1])
    return float(np.mean([len(np.intersect1d(x, y)) / len(np.unique(x))
                          for x, y in zip(flat_a, flat_b)]))


def _timed_compile(fn, *args):
    import jax
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled, time.perf_counter() - t0


def phase_kernels(log, *, hkv=8, g=2, d=128, window=128, k_width=128,
                  n_slots=4, m_slot=13, nc=512) -> dict:
    """Each serving kernel vs its XLA oracle on the same random state.
    Returns the errors and compile seconds it logged."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import mita_decode as mdec
    from repro.kernels import ops

    res = {}
    key = jax.random.PRNGKey(SEED)
    base = mdec.DecodeConfig(window=window, k=k_width, s=1,
                             paged_impl="xla", prefill_impl="xla",
                             finalize_impl="xla")
    ctx = m_slot * window

    # -- paged decode: inline (serving) and external-finalize (fused
    # append) modes; the finalize stays on XLA on both sides
    t = jnp.asarray([ctx // 5, ctx // 2 + 7, ctx - 2, 5][:n_slots],
                    jnp.int32)
    act = jnp.asarray([True, True, True, False][:n_slots])
    st, pt = _random_state(key, base, n_slots, m_slot, hkv, d, t)
    kq = jax.random.split(jax.random.PRNGKey(SEED + 1), 3)
    q = jax.random.normal(kq[0], (n_slots, hkv, g, d)).astype(jnp.bfloat16)
    k_new = jax.random.normal(kq[1], (n_slots, hkv, d)).astype(jnp.bfloat16)
    v_new = jax.random.normal(kq[2], (n_slots, hkv, d)).astype(jnp.bfloat16)
    for external in (False, True):
        cfg_x = dataclasses.replace(base, external_finalize=external)
        cfg_k = dataclasses.replace(cfg_x, paged_impl="kernel")
        step_k, secs = _timed_compile(
            lambda s, *a: mdec.mita_paged_decode_step(s, *a, cfg_k),
            st, q, k_new, v_new, pt, t, act)
        _check("tpu_custom_call" in step_k.as_text(),
               "paged decode program has no Pallas kernel")
        o_k, s_k = step_k(st, q, k_new, v_new, pt, t, act)
        o_x, s_x = jax.jit(lambda s, *a: mdec.mita_paged_decode_step(
            s, *a, cfg_x))(st, q, k_new, v_new, pt, t, act)
        err = float(jnp.max(jnp.abs(o_k.astype(jnp.float32)
                                    - o_x.astype(jnp.float32))))
        pools_ok = bool(jnp.array_equal(s_k.k_pool[:-1], s_x.k_pool[:-1])
                        & jnp.array_equal(s_k.v_pool[:-1], s_x.v_pool[:-1]))
        name = f"paged_decode[{'external' if external else 'inline'}]"
        log(f"{name}: compile {secs:.2f}s max|out err| {err:.3e} "
            f"pools equal {pools_ok}")
        res[name] = {"compile_s": secs, "max_out_err": err}
        _check(err <= OUT_ATOL, f"{name} output error {err} > {OUT_ATOL}")
        _check(pools_ok, f"{name} appended pool rows differ")

    # -- paged finalize: due slots at window ends
    t_new = jnp.asarray([window, ctx // 2 // window * window, ctx,
                         2 * window][:n_slots], jnp.int32)
    due = jnp.asarray([True, True, True, False][:n_slots])
    st, pt = _random_state(jax.random.PRNGKey(SEED + 2), base, n_slots,
                           m_slot, hkv, d, t_new)
    cfg_k = dataclasses.replace(base, finalize_impl="kernel")
    fin_k, secs = _timed_compile(
        lambda s, *a: mdec.mita_paged_finalize(s, *a, cfg_k),
        st, pt, t_new, due)
    _check("tpu_custom_call" in fin_k.as_text(),
           "paged finalize program has no Pallas kernel")
    f_k = fin_k(st, pt, t_new, due)
    f_x = jax.jit(lambda s, *a: mdec.mita_paged_finalize(s, *a, base))(
        st, pt, t_new, due)
    lmq_err = float(jnp.max(jnp.abs(f_k.lm_q.astype(jnp.float32)
                                    - f_x.lm_q.astype(jnp.float32))))
    lmv_err = float(jnp.max(jnp.abs(f_k.lm_v.astype(jnp.float32)
                                    - f_x.lm_v.astype(jnp.float32))))
    overlap = _expert_overlap(f_k.expert_idx, f_x.expert_idx)
    log(f"paged_finalize: compile {secs:.2f}s max|lm_q err| {lmq_err:.3e} "
        f"max|lm_v err| {lmv_err:.3e} expert overlap {overlap:.4f}")
    res["paged_finalize"] = {"compile_s": secs, "max_lm_q_err": lmq_err,
                             "max_lm_v_err": lmv_err,
                             "expert_overlap": overlap}
    _check(lmq_err <= LMQ_ATOL, f"finalize lm_q error {lmq_err}")
    _check(lmv_err <= OUT_ATOL, f"finalize lm_v error {lmv_err}")
    _check(overlap >= EXPERT_OVERLAP_MIN, f"finalize overlap {overlap}")

    # -- batched chunk prefill: two chunks per slot from an empty state
    # (resume across dispatches), one non-window-aligned prompt (the n//m
    # landmark quirk), one inactive row
    cfg_x = base
    cfg_k = dataclasses.replace(base, prefill_impl="kernel")
    q_block = ops.select_prefill_q_block(nc, window, m_slot, k_width, g, d)
    _check(q_block is not None, "chunk prefill does not fit the budget")
    n_train = np.asarray([2 * nc, nc + 300, 2 * nc, 0][:n_slots])
    act = np.asarray([True, True, True, False][:n_slots])
    pt = jnp.asarray(_page_table(n_slots, m_slot))
    st0 = mdec.init_paged_state(hkv, d, n_slots * m_slot + 2, n_slots,
                                m_slot, base, jnp.bfloat16)
    states = {"kernel": st0, "xla": st0}
    slots = jnp.arange(n_slots, dtype=jnp.int32)
    kc = jax.random.split(jax.random.PRNGKey(SEED + 4), 6)
    worst = {"out": 0.0, "lm_q": 0.0, "lm_v": 0.0, "q_sum": 0.0}
    overlap = 1.0
    secs = 0.0
    for c in range(2):
        t0 = np.full(n_slots, c * nc, np.int32)
        nv = np.where(act, np.clip(n_train - t0, 0, nc), 0).astype(np.int32)
        a_c = jnp.asarray(act & (nv > 0))
        qc = jax.random.normal(kc[3 * c], (n_slots, hkv, g, nc, d)
                               ).astype(jnp.bfloat16)
        kk = jax.random.normal(kc[3 * c + 1], (n_slots, hkv, nc, d)
                               ).astype(jnp.bfloat16)
        vv = jax.random.normal(kc[3 * c + 2], (n_slots, hkv, nc, d)
                               ).astype(jnp.bfloat16)
        args = (qc, kk, vv, pt, slots, jnp.asarray(t0), jnp.asarray(nv),
                jnp.asarray(n_train, jnp.int32), a_c)
        if c == 0:
            chunk_k, secs = _timed_compile(
                lambda s, *a: mdec.mita_batched_chunk_prefill(s, *a, cfg_k),
                states["kernel"], *args)
            _check("tpu_custom_call" in chunk_k.as_text(),
                   "chunk prefill program has no Pallas kernel")
        o_k, states["kernel"] = chunk_k(states["kernel"], *args)
        o_x, states["xla"] = jax.jit(
            lambda s, *a: mdec.mita_batched_chunk_prefill(s, *a, cfg_x))(
                states["xla"], *args)
        for s in range(n_slots):
            if nv[s]:
                e = jnp.abs(o_k[s, :, :, : nv[s]].astype(jnp.float32)
                            - o_x[s, :, :, : nv[s]].astype(jnp.float32))
                worst["out"] = max(worst["out"], float(jnp.max(e)))
        sk, sx = states["kernel"], states["xla"]
        for f in ("lm_q", "lm_v", "q_sum"):
            e = jnp.abs(getattr(sk, f).astype(jnp.float32)
                        - getattr(sx, f).astype(jnp.float32))
            worst[f] = max(worst[f], float(jnp.max(e)))
        overlap = min(overlap, _expert_overlap(sk.expert_idx, sx.expert_idx))
        _check(bool(jnp.array_equal(sk.k_pool[:-1], sx.k_pool[:-1])
                    & jnp.array_equal(sk.v_pool[:-1], sx.v_pool[:-1])),
               f"chunk prefill pages differ after chunk {c}")
    log(f"chunk_prefill[q_block={q_block}]: compile {secs:.2f}s "
        f"max|out err| {worst['out']:.3e} max|lm_q err| "
        f"{worst['lm_q']:.3e} max|lm_v err| {worst['lm_v']:.3e} "
        f"max|q_sum err| {worst['q_sum']:.3e} expert overlap {overlap:.4f}")
    res["chunk_prefill"] = {"compile_s": secs, **{
        f"max_{k}_err": v for k, v in worst.items()},
        "expert_overlap": overlap}
    _check(worst["out"] <= OUT_ATOL, f"chunk out error {worst['out']}")
    _check(worst["lm_q"] <= LMQ_ATOL, f"chunk lm_q error {worst['lm_q']}")
    _check(worst["lm_v"] <= OUT_ATOL, f"chunk lm_v error {worst['lm_v']}")
    _check(worst["q_sum"] <= LMQ_ATOL * 8,
           f"chunk q_sum error {worst['q_sum']}")
    _check(overlap >= EXPERT_OVERLAP_MIN, f"chunk overlap {overlap}")
    return res


# -------------------------------------------------------------- serve ----

SERVE_ARGV = ["--arch", ARCH, "--engine", "continuous",
              "--prefill-chunk", "512", "--prefill-mode", "batched",
              "--sample-device", "fused", "--batch", "4",
              "--requests", "16", "--prompt-len", "1536", "--gen", "32"]


def phase_serve(log, argv=SERVE_ARGV) -> dict:
    """The serving CLI's main path; every request completes with the
    kernels in use and nothing supervised away."""
    import numpy as np
    from repro.configs.registry import get_arch
    from repro.launch import serve

    args = serve.parse_args(argv)
    vocab = get_arch(args.arch, smoke=args.smoke).model.vocab
    out = serve.run(args)
    st = out["stats"]
    done = out["finished"]
    complete = [f for f in done if f.reason == "complete"]
    tokens = sum(len(f.tokens) for f in complete)
    counters = {k: st[k] for k in (
        "prefill_kernel_fallbacks", "paged_kernel_fallbacks",
        "finalize_kernel_fallbacks", "retries", "degradation_level",
        "quarantined", "rejected", "deadline_expired")}
    log(f"serve: {len(complete)}/{args.requests} requests complete, "
        f"{tokens} tokens in {out['seconds']:.1f}s (compile included), "
        f"{st['chunks']} chunks in {st['prefill_dispatches']} prefill "
        f"dispatches, counters {counters}")
    _check(len(complete) == args.requests,
           f"only {len(complete)}/{args.requests} requests completed")
    _check(all(len(f.tokens) == args.gen for f in complete),
           "a request stopped short of its token budget")
    _check(all(np.all((f.tokens >= 0) & (f.tokens < vocab))
               for f in complete), "token ids out of the vocabulary")
    for k, v in counters.items():
        _check(v == 0, f"serve counter {k} = {v}")
    _log_kv_capacity(log, args)
    return {"requests": len(complete), "tokens": tokens,
            "seconds": out["seconds"], **counters}


def _log_kv_capacity(log, args) -> None:
    """The device's memory limit and peak beside the serve run's KV pool
    bytes (f32 pools, sized as `launch.serve.run` sizes them): what the
    pools cost in slots."""
    import jax
    from repro.configs.registry import get_arch
    from repro.core import mita_decode as mdec

    ms = jax.devices()[0].memory_stats() or {}
    cfg = get_arch(args.arch).model
    w = cfg.attn.window
    per_token = 2 * cfg.n_layers * cfg.n_kv * cfg.head_dim * 4  # K+V, f32
    pages = mdec.window_aligned(args.prompt_len + args.gen, w) // w
    pools = (2 * args.batch * pages * w + 1) * per_token
    log(f"memory: limit {ms.get('bytes_limit')} B, peak "
        f"{ms.get('peak_bytes_in_use')} B, KV pools {pools} B "
        f"({per_token} B per token)")


# ---------------------------------------------------- sharded training ----

def phase_sharded_train(log, *, steps=3, batch=8, seq=256, layers=4) -> dict:
    """A few train steps of qwen3-0.6b (full width, ``layers`` deep) on a
    2x2 ("data", "model") mesh vs the same steps on one device."""
    import dataclasses

    import jax
    import numpy as np
    from repro.configs.registry import ShapeSpec, get_arch
    from repro.data import DataConfig, synthetic_batch
    from repro.launch.mesh import make_mesh
    from repro.launch.steps import build_cell, family_fns
    from repro.optim import adamw_init

    arch = get_arch(ARCH)
    arch = dataclasses.replace(arch, model=dataclasses.replace(
        arch.model, n_layers=layers))
    fns = family_fns(arch)
    dcfg = DataConfig(vocab=arch.model.vocab, seq_len=seq,
                      global_batch=batch)
    devs = jax.devices()
    _check(len(devs) >= 4, f"needs 4 devices, found {len(devs)}")

    def losses(mesh):
        cell = build_cell(arch, ShapeSpec("smoke", "train", seq, batch),
                          mesh)
        with mesh:
            params = jax.jit(fns["init"], out_shardings=cell.in_shardings[0])(
                jax.random.PRNGKey(SEED))
            opt = jax.jit(adamw_init, out_shardings=cell.in_shardings[1])(
                params)
            step = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                           out_shardings=cell.out_shardings)
            out = []
            for i in range(steps):
                host = synthetic_batch(dcfg, i)
                params, opt, m = step(params, opt, {
                    "tokens": host["tokens"], "labels": host["labels"]})
                out.append(float(m["loss"]))
        return out, params

    t0 = time.perf_counter()
    l4, p4 = losses(make_mesh((2, 2), ("data", "model"), devices=devs[:4]))
    t4 = time.perf_counter() - t0
    t0 = time.perf_counter()
    l1, _ = losses(make_mesh((1, 1), ("data", "model"), devices=devs[:1]))
    t1 = time.perf_counter() - t0
    wq = p4["blocks"]["attn"]["wq"]
    spread = len(wq.sharding.device_set)
    shard_shape = wq.addressable_shards[0].data.shape
    diff = max(abs(a - b) for a, b in zip(l4, l1))
    log(f"sharded train: losses 2x2 {l4} vs 1 device {l1} "
        f"(max diff {diff:.3e}); wq {wq.shape} on {spread} devices, "
        f"shard {shard_shape}; {t4:.1f}s / {t1:.1f}s (compile included)")
    _check(all(np.isfinite(l4 + l1)), "non-finite loss")
    _check(diff <= LOSS_ATOL, f"loss diff {diff} > {LOSS_ATOL}")
    _check(spread == 4, f"wq lives on {spread} devices, not 4")
    _check(shard_shape != wq.shape, "wq is replicated, not sharded")
    return {"losses_2x2": l4, "losses_1": l1, "max_diff": diff}


# --------------------------------------------------------------- main ----

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded-training phase on a 2x2 "
                         "mesh against one device")
    args = ap.parse_args(argv)

    def log(msg):
        print(msg, flush=True)

    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repository sources at {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devs[0].platform})",
              file=sys.stderr)
        return 3
    from repro.launch.compile_cache import enable_compile_cache

    log(f"device: {devs[0].device_kind} x{len(devs)}; compile cache "
        f"{enable_compile_cache()}")
    phases = ([phase_sharded_train] if args.chips == 4
              else [phase_kernels, phase_serve])
    failed = []
    for phase in phases:
        try:
            phase(log)
        except SmokeFailure as e:       # later phases still report
            failed.append(f"{phase.__name__}: {e}")
            log(f"FAILED {failed[-1]}")
    if failed:
        print(f"chip_smoke: FAILED: {'; '.join(failed)}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
