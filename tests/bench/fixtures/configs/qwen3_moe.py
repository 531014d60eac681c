"""Model module of a Qwen3-style decoder whose MLP is a routed mixture of
experts with shared experts, and MiTA attention (a test fixture).

Named by ``"model": "qwen3_moe"``.  Keys as in Qwen3-MoE's and Trinity's
published ``config.json``: ``num_experts`` held here, ``num_experts_per_tok``
(top-K), ``num_shared_experts`` and ``moe_intermediate_size`` (each
expert's width).  Routing is dropless: the program's capacity factor is
E / K, so no expert's queue can overflow.  The router is kept in float32,
as the program keeps it.
"""

from __future__ import annotations

import numpy as np

from bench import flops
from bench.model import DTYPES, NORM_SPREAD, attn_config

FAMILY = "moe"

# published key -> the registry's `ModelConfig` attribute
REGISTRY_KEYS = {"hidden_size": "d_model", "moe_intermediate_size": "d_ff",
                 "num_hidden_layers": "n_layers",
                 "num_attention_heads": "n_heads",
                 "num_key_value_heads": "n_kv", "head_dim": "dh",
                 "vocab_size": "vocab", "rope_theta": "rope_theta",
                 "tie_word_embeddings": "tie_embeddings",
                 "num_experts": "n_experts",
                 "num_experts_per_tok": "moe_top_k",
                 "num_shared_experts": "n_shared_experts"}


def model_config(spec: dict):
    from repro.models.modules import ModelConfig

    e, k = spec["num_experts"], spec["num_experts_per_tok"]
    return ModelConfig(
        name=spec["name"],
        n_layers=spec["num_hidden_layers"],
        d_model=spec["hidden_size"],
        n_heads=spec["num_attention_heads"],
        n_kv=spec["num_key_value_heads"],
        head_dim=spec["head_dim"],
        d_ff=spec["moe_intermediate_size"],
        vocab=spec["vocab_size"],
        rope_theta=float(spec["rope_theta"]),
        qk_norm=True,
        norm_eps=float(spec["rms_norm_eps"]),
        tie_embeddings=bool(spec["tie_word_embeddings"]),
        attn=attn_config(spec),
        n_experts=e, moe_top_k=k,
        n_shared_experts=spec["num_shared_experts"],
        moe_capacity_factor=e / k,
        param_dtype=DTYPES[spec["dtypes"]["params"]],
        compute_dtype=DTYPES[spec["dtypes"]["compute"]],
        remat=False)


def param_shapes(spec: dict) -> dict:
    """Name -> (shape, init scale[, dtype]) of every weight, in the
    program's layout (layer weights stacked on axis 0; experts on axis
    1)."""
    d = spec["hidden_size"]
    h, kv, dh = (spec["num_attention_heads"], spec["num_key_value_heads"],
                 spec["head_dim"])
    f, v, n = spec["moe_intermediate_size"], spec["vocab_size"], \
        spec["num_hidden_layers"]
    e, fs = spec["num_experts"], spec["num_shared_experts"] * f
    dense = lambda din: 1.0 / np.sqrt(din)
    out = lambda din: dense(din) / np.sqrt(2 * n)
    shapes = {
        "emb.tok": ((v, d), 0.02),
        "blocks.ln1": ((n, d), NORM_SPREAD),
        "blocks.ln2": ((n, d), NORM_SPREAD),
        "blocks.attn.wq": ((n, d, h * dh), dense(d)),
        "blocks.attn.wk": ((n, d, kv * dh), dense(d)),
        "blocks.attn.wv": ((n, d, kv * dh), dense(d)),
        "blocks.attn.wo": ((n, h * dh, d), out(h * dh)),
        "blocks.attn.q_norm": ((n, dh), NORM_SPREAD),
        "blocks.attn.k_norm": ((n, dh), NORM_SPREAD),
        "blocks.moe.router": ((n, d, e), dense(d), "float32"),
        "blocks.moe.wi": ((n, e, d, f), dense(d)),
        "blocks.moe.wg": ((n, e, d, f), dense(d)),
        "blocks.moe.wo": ((n, e, f, d), out(f)),
        "ln_f": ((d,), NORM_SPREAD),
    }
    if fs:
        shapes.update({"blocks.moe.shared.wi": ((n, d, fs), dense(d)),
                       "blocks.moe.shared.wg": ((n, d, fs), dense(d)),
                       "blocks.moe.shared.wo": ((n, fs, d), out(fs))})
    if not spec["tie_word_embeddings"]:
        shapes["emb.head"] = ((d, v), dense(d))
    return shapes


# ------------------------------------------------------------- counts --

def layer_matmul_params(spec: dict) -> int:
    """Parameters of one layer's matrix products that a token touches:
    attention, the router over every expert held, the shared experts and
    the K experts it is routed to."""
    d, f = spec["hidden_size"], spec["moe_intermediate_size"]
    experts = spec["num_shared_experts"] + spec["num_experts_per_tok"]
    return (flops.attn_matmul_params(spec) + d * spec["num_experts"]
            + 3 * d * f * experts)


def token_flops(spec: dict, pos: int, prompt: bool, head: bool) -> int:
    per_layer = (2 * layer_matmul_params(spec)
                 + flops.attend_flops(spec, pos, prompt)
                 + flops.landmark_flops(spec, pos))
    return spec["num_hidden_layers"] * per_layer + (
        2 * spec["hidden_size"] * spec["vocab_size"] if head else 0)


def paged_decode(spec: dict, positions) -> tuple[int, int]:
    ops, nbytes = flops.paged_decode_layer(spec, positions)
    n = spec["num_hidden_layers"]
    return n * ops, n * nbytes


def chunk_prefill(spec: dict, rows) -> tuple[int, int]:
    ops, nbytes = flops.chunk_prefill_layer(spec, rows)
    n = spec["num_hidden_layers"]
    return n * ops, n * nbytes
