"""Plain float32 reference of a Qwen3-style decoder whose MLP is a routed
mixture of experts, with MiTA attention, as it is served (a test
fixture).

Independent of the program: it imports nothing of it, and reads only the
configuration file and the weights the benchmark made from the seed.
Attention, norms, rotary embedding, the head and the float8 control are
the qwen3 reference's (``qwen3_mita.py`` beside this file), used as they
are.  Each layer's MLP, token by token: router logits h·W_r over every
expert held, softmax, the top K kept and renormalised to sum 1; the
output is the sum over those K experts of weight × SwiGLU_e(h), plus the
shared experts' SwiGLU (one matrix of width shared × expert width).
"""

from __future__ import annotations

import functools
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np


def _qwen3():
    path = Path(__file__).with_name("qwen3_mita.py")
    spec = importlib.util.spec_from_file_location("bench_reference_qwen3",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


q3 = _qwen3()


def _swiglu(h, ws, low):
    return q3._mm(jax.nn.silu(q3._mm(h, ws["wg"], low))
                  * q3._mm(h, ws["wi"], low), ws["wo"], low)


def _moe(m, h, top_k, low):
    gates = jax.nn.softmax(q3._mm(h, m["router"], low), axis=-1)
    w, idx = jax.lax.top_k(gates, top_k)
    w = w / jnp.sum(w, axis=-1, keepdims=True)

    def token(ht, wt, it):
        return sum(wt[j] * _swiglu(ht, {name: m[name][it[j]]
                                        for name in ("wi", "wg", "wo")}, low)
                   for j in range(top_k))

    out = jax.vmap(token)(h, w, idx)
    if "shared" in m:
        out = out + _swiglu(h, m["shared"], low)
    return out


@functools.partial(jax.jit, static_argnames=("dims", "top_k", "low"))
def _layer(lp, x, n_prompt, n_valid, dims, top_k, low):
    h_, kv, dh, w, k_width, theta, eps = dims
    n = x.shape[0]
    g = h_ // kv
    pos = jnp.arange(n)
    a = lp["attn"]
    h = q3._rms(x, lp["ln1"], eps)
    q = q3._mm(h, a["wq"], low).reshape(n, h_, dh)
    k = q3._mm(h, a["wk"], low).reshape(n, kv, dh)
    v = q3._mm(h, a["wv"], low).reshape(n, kv, dh)
    q = q3._rope(q3._rms(q, a["q_norm"], eps), pos, theta)
    k = q3._rope(q3._rms(k, a["k_norm"], eps), pos, theta)
    q = q.reshape(n, kv, g, dh).transpose(1, 2, 0, 3)
    o = jax.vmap(q3._mita_group, in_axes=(0, 0, 0, None, None, None, None))(
        q, k.transpose(1, 0, 2), v.transpose(1, 0, 2), n_prompt, n_valid,
        w, k_width)
    x = x + q3._mm(o.transpose(2, 0, 1, 3).reshape(n, h_ * dh), a["wo"],
                   low)
    return x + _moe(lp["moe"], q3._rms(x, lp["ln2"], eps), top_k, low)


def logits(params: dict, spec: dict, tokens: np.ndarray, n_prompt: int,
           low: bool = False, pad_to: int = 0, rows: int = 0) -> jax.Array:
    """As the qwen3 reference's `logits`: next-token logits [T, V] at
    positions n_prompt-1 .. len(tokens)-1."""
    n_valid = len(tokens)
    n = -(-max(n_valid, pad_to) // q3.BLOCK) * q3.BLOCK
    padded = np.zeros(n, np.int32)
    padded[:n_valid] = tokens
    mita = spec["mita"]
    dims = (spec["num_attention_heads"], spec["num_key_value_heads"],
            spec["head_dim"], mita["window"], mita["expert_width"],
            float(spec["rope_theta"]), float(spec["rms_norm_eps"]))
    x = jnp.take(params["emb"]["tok"].astype(jnp.float32),
                 jnp.asarray(padded), axis=0)
    for i in range(spec["num_hidden_layers"]):
        lp = jax.tree.map(lambda a: a[i].astype(jnp.float32),
                          params["blocks"])
        x = _layer(lp, x, np.int32(n_prompt), np.int32(n_valid), dims,
                   spec["num_experts_per_tok"], low)
    emb = jax.tree.map(lambda a: a.astype(jnp.float32), params["emb"])
    t = n_valid - n_prompt + 1
    xs = jax.lax.dynamic_slice_in_dim(x, n_prompt - 1, max(t, rows))
    return q3._head(emb, params["ln_f"].astype(jnp.float32), xs,
                    float(spec["rms_norm_eps"]),
                    bool(spec["tie_word_embeddings"]), low)[:t]
