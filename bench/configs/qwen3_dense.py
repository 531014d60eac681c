"""Model module of a dense Qwen3 decoder with MiTA attention.

Named by a configuration's ``"model": "qwen3_dense"`` (`bench.model.module`).
It reads the keys of Qwen3's published ``config.json`` (hf Qwen/Qwen3-*):
pre-norm decoder, q/k RMSNorm (qk_norm), grouped-query attention, a SwiGLU
MLP of width ``intermediate_size`` in every layer, the head tied to the
embedding or its own matrix.
"""

from __future__ import annotations

import numpy as np

from bench import flops
from bench.model import DTYPES, NORM_SPREAD, attn_config

FAMILY = "dense"

# published key -> the registry's `ModelConfig` attribute
REGISTRY_KEYS = {"hidden_size": "d_model", "intermediate_size": "d_ff",
                 "num_hidden_layers": "n_layers",
                 "num_attention_heads": "n_heads",
                 "num_key_value_heads": "n_kv", "head_dim": "head_dim",
                 "vocab_size": "vocab", "rope_theta": "rope_theta",
                 "tie_word_embeddings": "tie_embeddings"}


def model_config(spec: dict):
    from repro.models.modules import ModelConfig

    return ModelConfig(
        name=spec["name"],
        n_layers=spec["num_hidden_layers"],
        d_model=spec["hidden_size"],
        n_heads=spec["num_attention_heads"],
        n_kv=spec["num_key_value_heads"],
        head_dim=spec["head_dim"],
        d_ff=spec["intermediate_size"],
        vocab=spec["vocab_size"],
        rope_theta=float(spec["rope_theta"]),
        qk_norm=True,
        norm_eps=float(spec["rms_norm_eps"]),
        tie_embeddings=bool(spec["tie_word_embeddings"]),
        attn=attn_config(spec),
        param_dtype=DTYPES[spec["dtypes"]["params"]],
        compute_dtype=DTYPES[spec["dtypes"]["compute"]],
        remat=False)


def param_shapes(spec: dict) -> dict:
    """Name -> (shape, init scale) of every weight, in the program's
    layout (layer weights stacked on axis 0)."""
    d = spec["hidden_size"]
    h, kv, dh = (spec["num_attention_heads"], spec["num_key_value_heads"],
                 spec["head_dim"])
    f, v, n = spec["intermediate_size"], spec["vocab_size"], \
        spec["num_hidden_layers"]
    dense = lambda din: 1.0 / np.sqrt(din)
    # residual-branch outputs scaled by 1/sqrt(2 * layers), as GPT-2 and
    # Megatron initialise them, so the random network stays well
    # conditioned with depth
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
        "blocks.ffn.wi": ((n, d, f), dense(d)),
        "blocks.ffn.wg": ((n, d, f), dense(d)),
        "blocks.ffn.wo": ((n, f, d), out(f)),
        "ln_f": ((d,), NORM_SPREAD),
    }
    if not spec["tie_word_embeddings"]:
        shapes["emb.head"] = ((d, v), dense(d))
    return shapes


# ------------------------------------------------------------- counts --

def layer_matmul_params(spec: dict) -> int:
    """Parameters of one layer's matrix products: attention and the
    SwiGLU MLP."""
    return flops.attn_matmul_params(spec) + 3 * spec["hidden_size"] \
        * spec["intermediate_size"]


def token_flops(spec: dict, pos: int, prompt: bool, head: bool) -> int:
    """Model operations for one token through every layer (and the head
    when its next token is sampled)."""
    per_layer = (2 * layer_matmul_params(spec)
                 + flops.attend_flops(spec, pos, prompt)
                 + flops.landmark_flops(spec, pos))
    return spec["num_hidden_layers"] * per_layer + (
        2 * spec["hidden_size"] * spec["vocab_size"] if head else 0)


def paged_decode(spec: dict, positions) -> tuple[int, int]:
    """The paged decode kernel's (operations, bytes) over every layer:
    each is a MiTA layer."""
    ops, nbytes = flops.paged_decode_layer(spec, positions)
    n = spec["num_hidden_layers"]
    return n * ops, n * nbytes


def chunk_prefill(spec: dict, rows) -> tuple[int, int]:
    """The chunk-prefill kernel's (operations, bytes) over every layer."""
    ops, nbytes = flops.chunk_prefill_layer(spec, rows)
    n = spec["num_hidden_layers"]
    return n * ops, n * nbytes
