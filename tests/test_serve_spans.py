"""Stable device names of the serving programs: every jitted program a
backend dispatches lowers to a module named ``jit_<its name>``, which is
the name the profiler's trace and the benchmark's readers see."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import mamba2 as m2
from repro.models import transformer as tfm
from repro.models.modules import AttnConfig, ModelConfig
from repro.serve import EngineConfig
from repro.serve.backends import mita, recurrent
from repro.serve.backends.mita import MiTABackend
from repro.serve.backends.recurrent import Mamba2Backend

W, S, M = 8, 2, 4          # window, slots, pages per slot


@functools.lru_cache(maxsize=None)
def _backend(family):
    key = jax.random.PRNGKey(0)
    ecfg = EngineConfig(n_slots=S, pages_per_slot=M, n_pages=2 * M,
                        prefill_chunk=W, sample_device="fused")
    if family == "mita":
        cfg = ModelConfig(n_layers=2, d_model=64, n_heads=4, n_kv=2,
                          d_ff=128, vocab=97,
                          attn=AttnConfig(window=W, k=W, backend="mita_ref"))
        return MiTABackend(tfm.lm_init(key, cfg), cfg, ecfg)
    cfg = ModelConfig(n_layers=2, d_model=32, n_heads=1, n_kv=1, d_ff=0,
                      vocab=97, attn=AttnConfig(window=W, backend="full"))
    return Mamba2Backend(m2.mamba_init(key, cfg), cfg, ecfg)


def _i32(*shape):
    return np.zeros(shape, np.int32)


def _batch():
    """Per-slot decode inputs: tokens, positions, activity, rid, sample
    index, temperature and the sampling key."""
    vec = _i32(S)
    return (vec, vec, np.ones(S, bool), vec, vec, np.zeros(S, np.float32),
            jax.random.PRNGKey(0))


def _mita_lowered(name):
    be = _backend("mita")
    cfg, p, st = be.cfg, be.params, be.states
    tok, t, ac, rid, si, temp, key = _batch()
    pt = _i32(S, M)
    if name == "mita_decode_step":
        return mita._decode_fn(cfg, True, True).lower(
            p, st, tok, t, t, pt, ac, rid, si, temp, key)
    if name == "mita_batched_chunk_prefill":
        return mita._batched_chunk_prefill_fn(cfg, W, M).lower(
            p, st, _i32(1, W), np.ones(1, bool), _i32(1, M), _i32(1),
            _i32(1), _i32(1), _i32(1))
    if name == "mita_chunk_prefill":
        one = np.int32(W)
        return mita._chunk_prefill_fn(cfg, W, M).lower(
            p, st, _i32(W), np.int32(0), _i32(M), np.int32(0), one, one)
    if name == "mita_prefill_pack":
        return mita._prefill_pack_fn(cfg, W, 1).lower(
            p, st, _i32(1, W), _i32(1), _i32(1, 1))
    if name == "mita_draft":
        return mita._draft_fn(cfg, 1).lower(p, st, tok, t, ac, t, rid, si,
                                            temp, key)
    if name == "mita_verify":
        return mita._verify_fn(cfg, True, 2).lower(
            p, st, _i32(2, S), t, t, pt, ac, rid, si, temp, key, t)
    if name == "mita_rollback":
        q_stack = jnp.zeros((2,) + st.q_sum.shape, st.q_sum.dtype)
        return mita._rollback_fn(cfg).lower(st, q_stack, np.ones(S, np.int32))
    assert name == "mita_attach_prefix"
    rows = [jnp.zeros(a.shape[:1] + a.shape[2:], a.dtype)
            for a in (st.lm_q, st.lm_v, st.expert_idx, st.expert_valid)]
    return mita._attach_prefix_fn(cfg).lower(st, np.int32(0), *rows)


def _recurrent_lowered(name):
    be = _backend("mamba2")
    cfg, p, st = be.cfg, be.params, be.states
    tok, t, ac, rid, si, temp, key = _batch()
    if name == "recurrent_decode_step":
        return recurrent._decode_fn("mamba2", cfg, True).lower(
            p, st, tok, t, ac, rid, si, temp, key)
    if name == "recurrent_chunk_prefill":
        return recurrent._chunk_fn("mamba2", cfg).lower(
            p, st, _i32(1), _i32(1, W), _i32(1), _i32(1))
    if name == "recurrent_draft":
        return recurrent._spec_draft_fn("mamba2", cfg, 1).lower(
            p, st, tok, t, ac, rid, si, temp, key, t)
    assert name == "recurrent_teacher_forced"
    return recurrent._spec_tf_fn("mamba2", cfg, 2).lower(
        p, st, _i32(2, S), t, ac, rid, si, temp, key, t)


@pytest.mark.parametrize("name", [
    "mita_decode_step", "mita_batched_chunk_prefill", "mita_chunk_prefill",
    "mita_prefill_pack", "mita_draft", "mita_verify", "mita_rollback",
    "mita_attach_prefix", "recurrent_decode_step",
    "recurrent_chunk_prefill", "recurrent_draft",
    "recurrent_teacher_forced"])
def test_serving_program_module_name(name):
    lower = _mita_lowered if name.startswith("mita") else _recurrent_lowered
    text = lower(name).as_text()
    assert text.startswith(f"module @jit_{name} "), text[:120]
