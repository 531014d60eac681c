"""Compile the serving kernels for a described TPU v5e chip.

No chip is needed: the TPU compiler is installed, and it compiles for a
v5e that is described (`jax.experimental.topologies`) rather than
attached.  This catches what interpret mode cannot — block shapes Mosaic
refuses, unaligned slices, scalar reads it cannot lower, and kernels over
their VMEM limit — before any chip time is spent.  Each kernel is
compiled with the scoped-VMEM limit the dispatcher would give it, so
these tests also hold the `kernels.ops` working-set estimators to what
the compiler needs.

Widths: qwen3-0.6b (8 KV heads, 2 query heads per group, head dim 128),
tinyllama-1.1b (4 KV heads, 8 per group, head dim 64) and qwen3-32b-pp16
(8 KV heads, 8 per group, head dim 128; the benchmark's cells, whose
slots hold 48 and 66 landmarks), MiTA window and expert width 128, 4
slots; f32 pools whose head rows are whole 128-lane tiles
(`ops.pool_lanes`).

The topology is described inside a module fixture (never at import), and
JAX's persistent compilation cache is off around these compiles — an
entry compiled for a described chip cannot be read back without one.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import mita_chunk_prefill as mcp
from repro.kernels import mita_paged_attn as mpa
from repro.kernels import mita_paged_finalize as mpf
from repro.kernels import ops

ARCHS = {"qwen3-0.6b": dict(hkv=8, g=2, d=128),
         "tinyllama-1.1b": dict(hkv=4, g=8, d=64),
         "qwen3-32b-pp16": dict(hkv=8, g=8, d=128)}
W, K, SLOTS, NC = 128, 128, 4, 512


def _lanes(d):
    """`ops.pool_lanes` as on the TPU (the CPU test backend keeps d)."""
    return -(-d // ops.LANES) * ops.LANES


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2, with the persistent compile
    cache off for the module's compiles (restored afterwards)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _pools(dev, hkv, d, m, lanes=None):
    shape = (2 * SLOTS * m * W + 1, hkv, lanes or _lanes(d))
    return _spec(dev, shape, jnp.float32), _spec(dev, shape, jnp.float32)


def _paged_args(dev, hkv, g, d, m, lanes=None):
    f32, i32, bf = jnp.float32, jnp.int32, jnp.bfloat16
    return (_spec(dev, (SLOTS, hkv, g, d), bf),
            _spec(dev, (SLOTS, hkv, d), bf), _spec(dev, (SLOTS, hkv, d), bf),
            _spec(dev, (SLOTS, hkv, m, d), bf),
            _spec(dev, (SLOTS, hkv, m, d), bf),
            _spec(dev, (SLOTS, hkv, m, K), i32),
            _spec(dev, (SLOTS, hkv, m, K), jnp.bool_),
            *_pools(dev, hkv, d, m, lanes),
            _spec(dev, (SLOTS, m), i32), _spec(dev, (SLOTS,), i32),
            _spec(dev, (SLOTS,), jnp.bool_), _spec(dev, (SLOTS,), i32))


def _finalize_args(dev, hkv, d, m):
    f32, i32, bf = jnp.float32, jnp.int32, jnp.bfloat16
    return (_spec(dev, (SLOTS, hkv, d), f32),
            _spec(dev, (SLOTS, hkv, m, d), bf),
            _spec(dev, (SLOTS, hkv, m, d), bf),
            _spec(dev, (SLOTS, hkv, m, K), i32),
            _spec(dev, (SLOTS, hkv, m, K), jnp.bool_),
            *_pools(dev, hkv, d, m),
            _spec(dev, (SLOTS, m), i32), _spec(dev, (SLOTS,), i32),
            _spec(dev, (SLOTS,), jnp.bool_))


def _chunk_args(dev, hkv, g, d, m):
    f32, i32, bf = jnp.float32, jnp.int32, jnp.bfloat16
    return (_spec(dev, (SLOTS, hkv, g, NC, d), bf),
            _spec(dev, (SLOTS, hkv, NC, d), bf),
            _spec(dev, (SLOTS, hkv, NC, d), bf),
            _spec(dev, (SLOTS, hkv, m, d), bf),
            _spec(dev, (SLOTS, hkv, m, d), bf),
            _spec(dev, (SLOTS, hkv, m, K), i32),
            _spec(dev, (SLOTS, hkv, m, K), jnp.bool_),
            _spec(dev, (SLOTS, hkv, d), f32),
            _spec(dev, (SLOTS, hkv, m, d), bf),
            _spec(dev, (SLOTS, hkv, d), f32),
            *_pools(dev, hkv, d, m),
            _spec(dev, (SLOTS, m), i32), _spec(dev, (SLOTS,), i32),
            _spec(dev, (SLOTS,), i32), _spec(dev, (SLOTS,), i32),
            _spec(dev, (SLOTS,), jnp.bool_))


def _compile_paged(dev, hkv, g, d, m=17, n_route=1, lanes=None):
    limit = ops.kernel_vmem_limit(
        ops.paged_attention_vmem_bytes(W, m, K, g, lanes or _lanes(d)))
    return mpa.mita_paged_attention.lower(
        *_paged_args(dev, hkv, g, d, m, lanes), window=W, n_route=n_route,
        vmem_limit=limit).compile()


def _compile_finalize(dev, hkv, d, m=17):
    limit = ops.kernel_vmem_limit(
        ops.paged_finalize_vmem_bytes(W, m, K, _lanes(d)))
    return mpf.mita_paged_finalize_fused.lower(
        *_finalize_args(dev, hkv, d, m), window=W, k_width=K,
        vmem_limit=limit).compile()


def _compile_chunk(dev, hkv, g, d, m=17):
    q_block = ops.select_prefill_q_block(NC, W, m, K, g, _lanes(d))
    assert q_block is not None, "chunk prefill does not fit the budget"
    limit = ops.kernel_vmem_limit(ops.chunk_prefill_vmem_bytes(
        NC, W, m, K, g, _lanes(d), q_block=q_block))
    return mcp.mita_chunk_prefill_fused.lower(
        *_chunk_args(dev, hkv, g, d, m), window=W, k_width=K,
        q_block=q_block, vmem_limit=limit).compile()


@pytest.mark.parametrize("arch,n_route,m", [
    pytest.param("qwen3-0.6b", 1, 17, id="qwen3-0.6b-1"),
    pytest.param("qwen3-0.6b", 2, 17, id="qwen3-0.6b-2"),
    pytest.param("tinyllama-1.1b", 1, 17, id="tinyllama-1.1b-1"),
    ("qwen3-32b-pp16", 1, 48), ("qwen3-32b-pp16", 1, 66)])
def test_paged_decode_kernel_compiles(one_chip, arch, n_route, m):
    compiled = _compile_paged(one_chip, **ARCHS[arch], m=m, n_route=n_route)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_paged_finalize_kernel_compiles(one_chip, arch):
    a = ARCHS[arch]
    compiled = _compile_finalize(one_chip, a["hkv"], a["d"])
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("arch,m", [("qwen3-0.6b", 17), ("qwen3-0.6b", 33),
                                    ("tinyllama-1.1b", 17)])
def test_chunk_prefill_kernel_compiles(one_chip, arch, m):
    """2k- and 4k-token contexts, each at the largest attention tile the
    default VMEM budget allows, under the limit derived from the
    estimator."""
    compiled = _compile_chunk(one_chip, **ARCHS[arch], m=m)
    assert "tpu_custom_call" in compiled.as_text()


def test_head_dim_64_pool_needs_lane_padding(one_chip):
    """Why `ops.pool_lanes` pads: with 64-lane head rows Mosaic cannot
    slice one head out of the pool (it shares a 128-lane tile with the
    next head) and refuses the kernel."""
    a = ARCHS["tinyllama-1.1b"]
    with pytest.raises(Exception, match="aligned to tiling"):
        _compile_paged(one_chip, **a, lanes=a["d"])



# every static keyword of the three kernels' jits
STATIC = ("window", "n_route", "fuse_append", "pipeline", "vmem_limit",
          "interpret", "k_width", "external_finalize", "q_block")


def _another_name(jitted):
    """The kernel's Python function under a jit named ``another_name``."""
    def another_name(*args, **static):
        return jitted.__wrapped__(*args, **static)
    return jax.jit(another_name, static_argnames=STATIC)


@pytest.mark.parametrize("module,name,compile_", [
    (mpa, "mita_paged_attention",
     lambda dev: _compile_paged(dev, **ARCHS["qwen3-0.6b"])),
    (mpf, "mita_paged_finalize_fused",
     lambda dev: _compile_finalize(dev, 8, 128)),
    (mcp, "mita_chunk_prefill_fused",
     lambda dev: _compile_chunk(dev, **ARCHS["qwen3-0.6b"]))])
def test_kernel_name_is_pinned(one_chip, monkeypatch, module, name,
                               compile_):
    """Each Pallas call carries its own ``name``: compiled under a wrapper
    of another name, the kernel's instruction keeps the name the
    profiler's trace and the benchmark's readers look for."""
    monkeypatch.setattr(module, name, _another_name(getattr(module, name)))
    calls = [line.split(" = ")[0].split()[-1].lstrip("%")
             for line in compile_(one_chip).as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert calls
    assert all(c.startswith(f"{name}.") for c in calls), calls
