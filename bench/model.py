"""The system under test, built from a configuration file.

A configuration file (``bench/configs/<name>.json``) states the model's
sizes under the keys of its published ``config.json``, the MiTA settings
and the dtypes it is served in.  This module turns it into the program's
`ModelConfig` and `EngineConfig`, and makes the weights from a seed on the
device, in the program's parameter layout and the type they are served in.
The weights belong to the benchmark: the plain reference reads the same
arrays, and nothing the program makes.
"""

from __future__ import annotations

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

BENCH = Path(__file__).resolve().parent

# every cell serves through these engine settings: 512-token batched
# chunked prefill, sampling fused into the decode program, the landmark
# finalize run only at window boundaries
PREFILL_CHUNK = 512

# spread of the RMSNorm weights around 1 (the program stores them as an
# offset from 1, so 0 would leave every norm an identity map)
NORM_SPREAD = 0.1

DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


def load_json(kind: str, name: str, bench_dir: Path = BENCH) -> dict:
    """``bench/<kind>/<name>.json``."""
    return json.loads((bench_dir / kind / f"{name}.json").read_text())


def load_config(name: str, bench_dir: Path = BENCH) -> dict:
    return load_json("configs", name, bench_dir)


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative whole number (wider than 32 bits
    too)."""
    state = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.PRNGKey(int(state[0]) >> 1)


def model_config(spec: dict):
    """The program's `ModelConfig` for a configuration file."""
    from repro.models.modules import AttnConfig, ModelConfig

    mita = spec["mita"]
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
        attn=AttnConfig(backend="mita", window=mita["window"],
                        k=mita["expert_width"], s=mita["routed_experts"],
                        vmem_budget=int(mita.get("vmem_budget_bytes", 0))),
        param_dtype=DTYPES[spec["dtypes"]["params"]],
        compute_dtype=DTYPES[spec["dtypes"]["compute"]],
        remat=False)


def arch_config(spec: dict):
    from repro.configs.registry import ArchConfig

    return ArchConfig(arch_id=spec["name"], family="dense",
                      model=model_config(spec))


def engine_config(slots: int, pages_per_slot: int, n_pages: int):
    from repro.serve import EngineConfig

    return EngineConfig(n_slots=slots, pages_per_slot=pages_per_slot,
                        n_pages=n_pages, prefill_chunk=PREFILL_CHUNK,
                        sample_device="fused", prefill_mode="batched",
                        finalize="external")


def backend_model_config(cfg, ecfg):
    """The model config as the MiTA backend runs it (external finalize
    follows the engine's finalize mode)."""
    import dataclasses

    return dataclasses.replace(cfg, attn=dataclasses.replace(
        cfg.attn, external_finalize=ecfg.finalize == "external"))


def prefill_widths(slots: int) -> list[int]:
    """Row widths of the batched chunk-prefill program: powers of two up
    to the slot count, and the slot count itself."""
    out, k = [], 1
    while k <= slots:
        out.append(k)
        k *= 2
    if out[-1] != slots:
        out.append(slots)
    return out


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


def init_params(spec: dict, key: jax.Array) -> dict:
    """Every weight from ``key``, as a nested dict in the program's layout
    (call under `jax.jit` to make them on the device in one program).
    Norm weights are stored as their offset from 1, as the program reads
    them."""
    dt = DTYPES[spec["dtypes"]["params"]]
    shapes = param_shapes(spec)
    keys = jax.random.split(key, len(shapes))
    tree: dict = {}
    for k, (name, (shape, scale)) in zip(keys, sorted(shapes.items())):
        node = tree
        *path, leaf = name.split(".")
        for p in path:
            node = node.setdefault(p, {})
        node[leaf] = (jax.random.normal(k, shape, jnp.float32)
                      * scale).astype(dt)
    return tree


def make_params(spec: dict, seed: int) -> dict:
    """The weights for ``seed``, made on the default device in one jitted
    call."""
    return jax.jit(lambda k: init_params(spec, k))(seed_key(seed))
