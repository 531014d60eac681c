"""The system under test, built from a configuration file.

A configuration file (``bench/configs/<name>.json``) states the model's
sizes under the keys of its published ``config.json``, the MiTA settings
and the dtypes it is served in, and names two modules beside it: its
plain reference (``"reference"``, `bench.check`) and its model module
(``"model"``, `module`), which knows the architecture's keys, the
program's `ModelConfig` and parameter layout, and its operation counts.
This module turns the file into the program's `ModelConfig` and
`EngineConfig` through that module, and makes the weights from a seed on
the device, in the program's parameter layout and the type they are
served in.  The weights belong to the benchmark: the plain reference reads
the same arrays, and nothing the program makes.
"""

from __future__ import annotations

import functools
import importlib.util
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

# the key under which a loaded configuration keeps its directory
CONFIGS_DIR = "configs_dir"


def load_json(kind: str, name: str, bench_dir: Path = BENCH) -> dict:
    """``bench/<kind>/<name>.json``."""
    return json.loads((bench_dir / kind / f"{name}.json").read_text())


def load_config(name: str, bench_dir: Path = BENCH) -> dict:
    """``bench/configs/<name>.json``, which remembers its directory: the
    model module it names is looked up beside it."""
    return {**load_json("configs", name, bench_dir),
            CONFIGS_DIR: str(bench_dir / "configs")}


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative whole number (wider than 32 bits
    too)."""
    state = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.PRNGKey(int(state[0]) >> 1)


def module(spec: dict):
    """The model module a configuration names under ``"model"``:
    ``<module>.py`` in the directory the configuration was loaded from
    (``bench/configs/`` by default).  It defines ``FAMILY`` (the registry
    family `serve.backends.for_arch` dispatches on), ``REGISTRY_KEYS``
    (published key -> `ModelConfig` attribute), ``model_config(spec)``,
    ``param_shapes(spec)`` and the counts `bench.flops` hands on:
    ``token_flops``, ``paged_decode`` and ``chunk_prefill``."""
    if "model" not in spec:
        raise ValueError(
            f"configuration {spec.get('name')!r} names no model module: "
            'give it "model": "<module>" for bench/configs/<module>.py')
    path = f"{spec.get(CONFIGS_DIR, BENCH / 'configs')}/{spec['model']}.py"
    try:
        return _load_module(path)
    except FileNotFoundError:
        raise FileNotFoundError(
            f"configuration {spec.get('name')!r} names the model module "
            f"{spec['model']!r}, but {path} does not exist") from None


@functools.lru_cache(maxsize=None)
def _load_module(path: str):
    """One module object per file (the counts are looked up per token)."""
    mspec = importlib.util.spec_from_file_location(
        f"bench_model_{Path(path).stem}", path)
    mod = importlib.util.module_from_spec(mspec)
    mspec.loader.exec_module(mod)
    return mod


def model_config(spec: dict):
    """The program's `ModelConfig` for a configuration file."""
    return module(spec).model_config(spec)


def arch_config(spec: dict):
    from repro.configs.registry import ArchConfig

    return ArchConfig(arch_id=spec["name"], family=module(spec).FAMILY,
                      model=model_config(spec))


def attn_config(spec: dict):
    """The program's MiTA `AttnConfig` from the configuration's ``mita``
    settings."""
    from repro.models.modules import AttnConfig

    mita = spec["mita"]
    return AttnConfig(backend="mita", window=mita["window"],
                      k=mita["expert_width"], s=mita["routed_experts"],
                      vmem_budget=int(mita.get("vmem_budget_bytes", 0)))


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
    """Name -> (shape, init scale[, dtype name]) of every weight, in the
    program's layout; a weight with no dtype of its own is made in the
    configuration's params dtype."""
    return module(spec).param_shapes(spec)


def init_params(spec: dict, key: jax.Array) -> dict:
    """Every weight from ``key``, as a nested dict in the program's layout
    (call under `jax.jit` to make them on the device in one program).
    Norm weights are stored as their offset from 1, as the program reads
    them."""
    dt = DTYPES[spec["dtypes"]["params"]]
    shapes = param_shapes(spec)
    keys = jax.random.split(key, len(shapes))
    tree: dict = {}
    for k, (name, (shape, scale, *own)) in zip(keys,
                                                sorted(shapes.items())):
        node = tree
        *path, leaf = name.split(".")
        for p in path:
            node = node.setdefault(p, {})
        node[leaf] = (jax.random.normal(k, shape, jnp.float32)
                      * scale).astype(DTYPES[own[0]] if own else dt)
    return tree


def make_params(spec: dict, seed: int) -> dict:
    """The weights for ``seed``, made on the default device in one jitted
    call."""
    return jax.jit(lambda k: init_params(spec, k))(seed_key(seed))
