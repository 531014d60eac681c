"""Serving driver: static batch or the continuous-batching engine.

Two paths over the same model/step functions:

  * ``--engine static``      — prefill a fixed batch of equal-length prompts,
    decode everyone for ``--gen`` steps (the PR-0 baseline; also the oracle
    the engine's greedy outputs are pinned against).
  * ``--engine continuous``  — `repro.serve.ServingEngine`: the generic
    scheduler over a `DecodeBackend` resolved from the registry
    architecture (`serve.backends.for_arch`) — the paged MiTA backend for
    attention LMs, constant-state recurrent backends for ssm/hybrid — so
    ANY registry architecture with a decode state is servable:

      PYTHONPATH=src python -m repro.launch.serve --arch mamba2-370m \\
          --smoke --engine continuous

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --smoke \
      --batch 4 --prompt-len 128 --gen 32 [--engine continuous] \
      [--prefill-chunk 256] [--priority 0] [--reserve-pages 2] \
      [--sample-device fused] [--prefill-mode batched] [--prefill-impl auto]

``--prefill-chunk N`` (continuous engine) admits prompts in N-token chunks
interleaved with the decode batch and enables priority preemption;
``--priority`` tags the generated requests' priority class and
``--reserve-pages`` keeps pages back for decode-time appends
(docs/serving.md explains all three).  ``--sample-device fused`` moves
sampling into the fused decode program so the hot loop downloads [S]
int32 tokens instead of [S, V] logits.

The continuous engine always runs SUPERVISED (`serve.Supervisor`):
``--max-retries`` sets the per-fault retry budget, ``--deadline-ms``
attaches a deadline to every generated request, and ``--chaos-seed`` /
``--chaos-rate`` wrap the backend in the seeded fault injector
(`serve.ChaosBackend`) to demonstrate retry / quarantine / degradation
end-to-end (docs/serving.md §Failure domains).
"""

from __future__ import annotations

import argparse
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import get_arch
from repro.data import DataConfig, synthetic_batch
from repro.core import mita_decode as mdec
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer as tfm
from repro.models.modules import ModelConfig


@functools.lru_cache(maxsize=None)
def _static_fns(cfg: ModelConfig, capacity: int):
    """Jitted static-path step functions, cached so repeated
    `static_generate` calls (per-batch in the benchmark) don't retrace."""
    return (jax.jit(lambda p, t: tfm.lm_prefill(p, t, cfg, capacity)),
            jax.jit(lambda p, st, tok, pos: tfm.lm_decode_step(
                p, st, tok, pos, cfg)),
            jax.jit(lambda st: tfm.lm_finalize_states(st, cfg)))


def static_generate(params, cfg: ModelConfig, prompts: jnp.ndarray, gen: int,
                    temperature: float = 0.0, capacity: int | None = None,
                    sample_key: jax.Array | None = None):
    """Fixed-batch prefill + decode.  prompts: [B, N] (equal length).

    Returns (tokens [B, gen], timings dict).  With ``cfg.attn.
    external_finalize`` the landmark finalize runs as its own program at
    window boundaries (tracking the prefill-finalized count so a
    boundary-aligned prompt is not re-finalized from an empty q_sum).
    """
    b, n = prompts.shape
    w = cfg.attn.window
    capacity = capacity or n + gen
    capacity = mdec.window_aligned(capacity, w)
    if sample_key is None:
        sample_key = jax.random.PRNGKey(1000)
    prefill, decode, finalize = _static_fns(cfg, capacity)

    t0 = time.perf_counter()
    logits, states = prefill(params, prompts)
    logits.block_until_ready()
    t_prefill = time.perf_counter() - t0

    def sample(lg, i):
        if temperature > 0:
            key = jax.random.fold_in(sample_key, i)
            return jax.random.categorical(
                key, lg / temperature, axis=-1).astype(jnp.int32)
        return jnp.argmax(lg, axis=-1).astype(jnp.int32)

    tok = sample(logits, 0)
    out_tokens = [tok]
    m_done = n // w
    step_times = []
    t0 = time.perf_counter()
    for i in range(gen - 1):
        pos = n + i
        if cfg.attn.external_finalize and pos % w == 0 and pos // w > m_done:
            states = finalize(states)
            m_done = pos // w
        ts = time.perf_counter()
        logits, states = decode(params, states, tok, jnp.asarray(pos))
        tok = sample(logits, i + 1)
        tok.block_until_ready()
        step_times.append(time.perf_counter() - ts)
        out_tokens.append(tok)
    t_decode = time.perf_counter() - t0

    gen_np = np.stack([np.asarray(t) for t in out_tokens], axis=1)
    return gen_np, {"prefill_s": t_prefill, "decode_s": t_decode,
                    "step_times": step_times}


def parse_args(argv=None) -> argparse.Namespace:
    """The serving CLI's arguments (validated)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--engine", choices=("static", "continuous"),
                    default="static")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=128)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--requests", type=int, default=0,
                    help="continuous: total requests (default 2x batch)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="continuous: chunked-prefill length in tokens "
                         "(multiple of the window; 0 = monolithic prefill)")
    ap.add_argument("--priority", type=int, default=0,
                    help="continuous: priority class for the generated "
                         "requests (higher wins admission/preemption)")
    ap.add_argument("--reserve-pages", type=int, default=0,
                    help="continuous: pages reserved for decode appends")
    ap.add_argument("--sample-device", choices=("host", "fused"),
                    default="host",
                    help="continuous: sample on the host from downloaded "
                         "[S, V] logits, or inside the fused decode "
                         "program (downloads [S] int32 tokens per step)")
    ap.add_argument("--prefill-mode", choices=("batched", "per-job"),
                    default="batched",
                    help="continuous+chunked: advance ALL prefilling slots "
                         "in one dispatch per step (batched), or one job "
                         "per step in its own dispatch (per-job, the "
                         "legacy baseline)")
    ap.add_argument("--prefill-impl", choices=("auto", "kernel", "xla"),
                    default="auto",
                    help="chunk-prefill backend: fused Pallas kernel when "
                         "it fits the VMEM budget (auto/kernel) or the XLA "
                         "oracle; REPRO_PREFILL_IMPL overrides")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="continuous+chunked: radix cache of committed "
                         "window-aligned prompt prefixes — repeated "
                         "prompts attach cached pages by reference and "
                         "skip straight to the first unshared chunk")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="continuous: lossless speculative decoding — "
                         "draft up to K tokens per slot per round and "
                         "verify them in one fused teacher-forced pass "
                         "(requires --sample-device fused; 0 = off)")
    ap.add_argument("--spec-mode", default="auto",
                    choices=("auto", "landmark", "self", "stress"),
                    help="drafting strategy: auto picks the backend's "
                         "native one (MiTA: landmark-branch self-draft; "
                         "recurrent: exact decode scan); stress forces "
                         "synthetic wrong drafts to exercise rollback")
    ap.add_argument("--chaos-seed", type=int, default=None,
                    help="continuous: wrap the backend in the seeded "
                         "fault injector (serve.ChaosBackend) and drive "
                         "the engine through the Supervisor — transient "
                         "faults, slot faults, and allocator spikes on "
                         "this seed's schedule")
    ap.add_argument("--chaos-rate", type=float, default=0.2,
                    help="chaos: per-dispatch new-fault probability")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="continuous: per-request deadline; requests "
                         "still unfinished when it expires are cancelled "
                         "with finish reason 'deadline_expired'")
    ap.add_argument("--max-retries", type=int, default=3,
                    help="supervisor: step retries before a fault "
                         "escalates to quarantine / degradation")
    args = ap.parse_args(argv)
    if args.prefix_cache and not args.prefill_chunk:
        ap.error("--prefix-cache requires --prefill-chunk > 0")
    if args.spec_k and args.sample_device != "fused":
        ap.error("--spec-k requires --sample-device fused (verification "
                 "samples inside the fused program)")
    if args.chaos_seed is not None and args.engine != "continuous":
        ap.error("--chaos-seed requires --engine continuous (the fault "
                 "injector wraps the DecodeBackend)")
    return args


def run(args: argparse.Namespace) -> dict:
    """Serve ``args``' generated requests and print the summary lines.

    Returns what was printed as data: ``seconds``, ``requests``, ``tokens``
    (generated tokens over finished requests), ``finished`` (the
    `FinishedRequest` list; continuous engine only) and ``stats`` (the
    engine's `stats()`; continuous engine only)."""
    arch = get_arch(args.arch, smoke=args.smoke)
    if arch.family not in ("dense", "moe", "vlm", "ssm", "hybrid"):
        raise SystemExit("serve.py drives decoder LMs (attention, ssm, "
                         "hybrid); use examples/ for whisper serving")
    cfg = arch.model
    if args.prefill_impl != "auto":
        import dataclasses
        cfg = dataclasses.replace(cfg, attn=dataclasses.replace(
            cfg.attn, prefill_impl=args.prefill_impl))
        arch = dataclasses.replace(arch, model=cfg)
    w = cfg.attn.window

    # registry-routed construction: family -> init fn -> DecodeBackend,
    # so every servable architecture rides the same driver
    from repro.configs.registry import arch_params
    from repro.serve import EngineConfig, Request, ServingEngine, backends

    params = arch_params(arch, jax.random.PRNGKey(0))
    dcfg = DataConfig(vocab=cfg.vocab, seq_len=args.prompt_len,
                      global_batch=max(args.batch, args.requests or 1))
    prompts = np.asarray(synthetic_batch(dcfg, 0)["tokens"])
    pages = mdec.window_aligned(args.prompt_len + args.gen, w) // w
    ecfg = EngineConfig(n_slots=args.batch, pages_per_slot=pages,
                        n_pages=2 * args.batch * pages,
                        prefill_chunk=args.prefill_chunk,
                        reserve_pages=args.reserve_pages,
                        sample_device=args.sample_device,
                        prefill_mode=args.prefill_mode,
                        prefix_cache=args.prefix_cache,
                        spec_k=args.spec_k, spec_mode=args.spec_mode)

    if args.engine == "static" and arch.family in ("dense", "moe", "vlm"):
        gen, tm = static_generate(params, cfg,
                                  jnp.asarray(prompts[: args.batch]),
                                  args.gen, temperature=args.temperature)
        tps = args.batch * (args.gen - 1) / max(tm["decode_s"], 1e-9)
        print(f"prefill: {args.batch}x{args.prompt_len} in "
              f"{tm['prefill_s']:.3f}s")
        print(f"decode:  {args.gen - 1} steps, {tm['decode_s']:.3f}s "
              f"({tps:.1f} tok/s, batch={args.batch})")
        sample = gen
        summary = {"seconds": tm["prefill_s"] + tm["decode_s"],
                   "requests": args.batch, "tokens": int(gen.size)}
    elif args.engine == "static":
        backend = backends.for_arch(arch, params, ecfg)
        t0 = time.perf_counter()
        gen = backend.static_reference(prompts[: args.batch], args.gen,
                                       temperature=args.temperature)
        dt = time.perf_counter() - t0
        print(f"static ({backend.name}): {args.batch}x{args.prompt_len}"
              f"+{args.gen} in {dt:.3f}s "
              f"({args.batch * args.gen / dt:.1f} tok/s)")
        sample = gen
        summary = {"seconds": dt, "requests": args.batch,
                   "tokens": int(np.asarray(gen).size)}
    else:
        from repro.serve import ChaosBackend, ChaosConfig, Supervisor, \
            SupervisorConfig

        n_req = args.requests or 2 * args.batch
        backend = backends.for_arch(arch, params, ecfg)
        if args.chaos_seed is not None:
            # faults are gated at ops whose injection fires before any
            # state mutation, so supervised retries stay bit-exact on
            # every backend (recurrent self-drafters included)
            backend = ChaosBackend(backend, ChaosConfig(
                seed=args.chaos_seed, p_fault=args.chaos_rate,
                transient_len=2, p_slot_fault=0.3,
                alloc_spike_every=8, alloc_spike_pages=2,
                ops=("decode_step", "prefill_chunks", "prefill_chunk",
                     "prefill_group", "draft_steps")))
        eng = ServingEngine(params, cfg, ecfg, backend=backend)
        sup = Supervisor(eng, SupervisorConfig(
            max_retries=args.max_retries))
        reqs = [Request(rid=i, prompt=prompts[i % len(prompts)],
                        max_new_tokens=args.gen,
                        temperature=args.temperature,
                        priority=args.priority,
                        deadline_ms=args.deadline_ms)
                for i in range(n_req)]
        t0 = time.perf_counter()
        done = sup.run(reqs)
        dt = time.perf_counter() - t0
        sup.close()
        total = sum(len(f.tokens) for f in done)
        st = eng.stats()
        print(f"continuous[{st['backend']}]: {n_req} requests "
              f"({args.prompt_len}+{args.gen}) "
              f"in {dt:.3f}s — {total / dt:.1f} tok/s, "
              f"{eng.steps} fused steps, batch={args.batch}, "
              f"chunks={st['chunks']} in "
              f"{st['prefill_dispatches']} dispatches, "
              f"preemptions={st['preemptions']}, "
              f"pages_hw={st['pages_high_water']}, "
              f"prefill_kernel_fallbacks={st['prefill_kernel_fallbacks']}, "
              f"paged_kernel_fallbacks={st['paged_kernel_fallbacks']}, "
              f"finalize_kernel_fallbacks="
              f"{st['finalize_kernel_fallbacks']}, "
              f"prefix_hits={st['prefix_cache_hits']}, "
              f"pages_shared={st['pages_shared']}, "
              f"spec_accepted={st['spec_accepted']}/"
              f"{st['spec_drafted']}, "
              f"rejected={st['rejected']}, "
              f"deadline_expired={st['deadline_expired']}, "
              f"retries={st['retries']}, "
              f"quarantined={st['quarantined']}, "
              f"degradation_level={st['degradation_level']}")
        full = [f.tokens for f in done if f.reason == "complete"] \
            or [f.tokens for f in done]
        sample = np.stack(full[:2]) if full[0].size else np.zeros((1, 16))
        summary = {"seconds": dt, "requests": n_req, "tokens": total,
                   "finished": done, "stats": st}
    print("sample generations (token ids):")
    for b in range(min(2, sample.shape[0])):
        print(f"  [{b}] {sample[b, :16].tolist()}")
    return summary


def main(argv=None):
    run(parse_args(argv))
    return 0


if __name__ == "__main__":
    enable_compile_cache()
    raise SystemExit(main())
