"""Plain float32 reference of a Qwen3 decoder with MiTA attention, as it
is served.

Independent of the program: it imports nothing of it, and reads only the
configuration file and the weights the benchmark made from the seed (the
program's parameter layout: layer weights stacked on axis 0, RMSNorm
weights stored as their offset from 1).

Qwen3 (hf Qwen/Qwen3-*): pre-norm decoder; RMSNorm; q/k projections with
a per-head RMSNorm (qk_norm) and rotary embedding (rotate-half, theta from
the config); grouped-query attention; SwiGLU MLP; final RMSNorm; the head
tied to the embedding or its own matrix.

MiTA attention (arXiv:2602.01219) in the causal serving form the program
implements, for a prompt of ``n`` tokens and window ``w``:

  * window i's landmark query is the mean, over the window's w positions,
    of the group's query heads' mean query; its keys are all positions
    < (i+1)·w; its expert is the top-k of those keys by score against
    the landmark query, and its landmark value the softmax-weighted mean
    of their values (one landmark set per KV-head group);
  * a query at position p sees landmark i when (i+1)·w <= p + 1 inside
    the prompt (p < n), and when (i+1)·w <= p for generated tokens: a
    generated token's own window closes only after it is served;
  * each query head attends, in one softmax, to the visible landmarks
    (keys: landmark queries, values: landmark values), to the k keys of
    the one landmark it scores highest, and to its own window's positions
    up to p.  A key in both the expert and the local window counts twice,
    as in the program.

Everything is float32 with matrix products at ``precision=HIGHEST``.
``low=True`` is the control: every weight matrix product takes its two
operands rounded to float8 (e4m3), the precision below the bfloat16 the
configuration serves in.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
NEG = -1e30
BLOCK = 512          # query rows per attention block; sequences pad to it


def _mm(a, b, low):
    if low:
        a = a.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        b = b.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return jnp.matmul(a, b, precision=HI)


def _rms(x, offset, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + offset)


def _rope(x, pos, theta):
    """x: [L, heads, d]; pos: [L]."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _mita_group(q, k, v, n_prompt, n_valid, w, k_width):
    """One KV-head group.  q: [G, L, d]; k, v: [L, d] -> [G, L, d]."""
    g, n, d = q.shape
    m = n // w
    scale = 1.0 / math.sqrt(d)
    pos = jnp.arange(n)
    ends = (jnp.arange(m) + 1) * w
    lm_q = jnp.mean(q, axis=0)[: m * w].reshape(m, w, d).mean(axis=1)
    s_lm = jnp.matmul(lm_q, k.T, precision=HI) * scale        # [m, L]
    vis = (pos[None, :] < ends[:, None]) & (pos[None, :] < n_valid)
    s_lm = jnp.where(vis, s_lm, NEG)
    top, idx = jax.lax.top_k(s_lm, k_width)                   # [m, K]
    member = jnp.zeros((m, n), jnp.float32).at[
        jnp.arange(m)[:, None], idx].add((top > NEG / 2).astype(jnp.float32))
    p_lm = jnp.where(vis, jnp.exp(s_lm - s_lm.max(-1, keepdims=True)), 0.0)
    # windows past the sequence's end see no key; keep their values 0
    norm = jnp.maximum(p_lm.sum(-1, keepdims=True), 1e-30)
    lm_v = jnp.matmul(p_lm / norm, v, precision=HI)

    def block(args):
        qb, pb = args                                         # [G,B,d], [B]
        off = (pb < n_prompt).astype(jnp.int32)
        avail = ends[None, :] <= (pb + off)[:, None]          # [B, m]
        r = jnp.einsum("gbd,md->gbm", qb, lm_q, precision=HI) * scale
        r = jnp.where(avail[None], r, NEG)
        route = jnp.argmax(r, axis=-1)                        # [G, B]
        routed = member[route] * avail.any(-1)[None, :, None]  # [G, B, L]
        local = ((pos[None, :] // w == pb[:, None] // w)
                 & (pos[None, :] <= pb[:, None]))
        wt = routed + local[None].astype(jnp.float32)
        s = jnp.einsum("gbd,ld->gbl", qb, k, precision=HI) * scale
        s = jnp.where(wt > 0, s, NEG)
        top_m = jnp.maximum(r.max(-1), s.max(-1))[..., None]
        pr = jnp.where(avail[None], jnp.exp(r - top_m), 0.0)
        ps = wt * jnp.exp(s - top_m)
        num = (jnp.einsum("gbm,md->gbd", pr, lm_v, precision=HI)
               + jnp.einsum("gbl,ld->gbd", ps, v, precision=HI))
        return num / (pr.sum(-1) + ps.sum(-1))[..., None]

    nb = n // BLOCK
    qs = q.reshape(g, nb, BLOCK, d).transpose(1, 0, 2, 3)
    out = jax.lax.map(block, (qs, pos.reshape(nb, BLOCK)))    # [nb,G,B,d]
    return out.transpose(1, 0, 2, 3).reshape(g, n, d)


@functools.partial(jax.jit, static_argnames=("dims", "low"))
def _layer(lp, x, n_prompt, n_valid, dims, low):
    h_, kv, dh, w, k_width, theta, eps = dims
    n = x.shape[0]
    g = h_ // kv
    pos = jnp.arange(n)
    a = lp["attn"]
    h = _rms(x, lp["ln1"], eps)
    q = _mm(h, a["wq"], low).reshape(n, h_, dh)
    k = _mm(h, a["wk"], low).reshape(n, kv, dh)
    v = _mm(h, a["wv"], low).reshape(n, kv, dh)
    q = _rope(_rms(q, a["q_norm"], eps), pos, theta)
    k = _rope(_rms(k, a["k_norm"], eps), pos, theta)
    q = q.reshape(n, kv, g, dh).transpose(1, 2, 0, 3)         # [kv,G,L,d]
    o = jax.vmap(_mita_group, in_axes=(0, 0, 0, None, None, None, None))(
        q, k.transpose(1, 0, 2), v.transpose(1, 0, 2), n_prompt, n_valid,
        w, k_width)
    o = o.transpose(2, 0, 1, 3).reshape(n, h_ * dh)
    x = x + _mm(o, a["wo"], low)
    h = _rms(x, lp["ln2"], eps)
    f = lp["ffn"]
    return x + _mm(jax.nn.silu(_mm(h, f["wg"], low)) * _mm(h, f["wi"], low),
                   f["wo"], low)


@functools.partial(jax.jit, static_argnames=("eps", "tied", "low"))
def _head(emb, ln_f, x, eps, tied, low):
    xf = _rms(x, ln_f, eps)
    w = emb["tok"].T if tied else emb["head"]
    return _mm(xf, w, low)


def logits(params: dict, spec: dict, tokens: np.ndarray, n_prompt: int,
           low: bool = False, pad_to: int = 0, rows: int = 0) -> jax.Array:
    """Next-token logits [T, V] at positions n_prompt-1 .. len(tokens)-1,
    for ``tokens`` = a prompt of ``n_prompt`` ids and the tokens served
    after it, less the last.  The sequence is padded (to a multiple of
    the attention block, and to at least ``pad_to``) with positions that
    no valid position attends, and the head runs over at least ``rows``
    positions: with both fixed, every request of a run shares one
    compiled program."""
    n_valid = len(tokens)
    n = -(-max(n_valid, pad_to) // BLOCK) * BLOCK
    padded = np.zeros(n, np.int32)
    padded[:n_valid] = tokens
    mita = spec["mita"]
    dims = (spec["num_attention_heads"], spec["num_key_value_heads"],
            spec["head_dim"], mita["window"], mita["expert_width"],
            float(spec["rope_theta"]), float(spec["rms_norm_eps"]))
    x = jnp.take(params["emb"]["tok"].astype(jnp.float32),
                 jnp.asarray(padded), axis=0)
    blocks = params["blocks"]
    for i in range(spec["num_hidden_layers"]):
        lp = jax.tree.map(lambda a: a[i].astype(jnp.float32), blocks)
        x = _layer(lp, x, np.int32(n_prompt), np.int32(n_valid), dims, low)
    emb = jax.tree.map(lambda a: a.astype(jnp.float32), params["emb"])
    ln_f = params["ln_f"].astype(jnp.float32)
    t = n_valid - n_prompt + 1
    xs = jax.lax.dynamic_slice_in_dim(x, n_prompt - 1, max(t, rows))
    return _head(emb, ln_f, xs, float(spec["rms_norm_eps"]),
                 bool(spec["tie_word_embeddings"]), low)[:t]
