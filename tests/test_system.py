"""End-to-end system tests: the real launch drivers on reduced configs."""

import numpy as np
import pytest


def test_train_driver_end_to_end(tmp_path):
    from repro.launch.train import main
    rc = main(["--arch", "qwen3-0.6b", "--smoke", "--steps", "6",
               "--batch", "4", "--seq", "64",
               "--ckpt-dir", str(tmp_path / "ckpt"), "--ckpt-every", "3"])
    assert rc == 0
    from repro.checkpoint import CheckpointManager
    mgr = CheckpointManager(str(tmp_path / "ckpt"))
    assert mgr.latest_step() == 6


def test_train_resume_after_failure(tmp_path):
    from repro.launch.train import main
    ckpt = str(tmp_path / "ckpt")
    with pytest.raises(RuntimeError, match="simulated node failure"):
        main(["--arch", "tinyllama-1.1b", "--smoke", "--steps", "8",
              "--batch", "4", "--seq", "64", "--ckpt-dir", ckpt,
              "--ckpt-every", "2", "--simulate-failure", "5"])
    # restart resumes from the last checkpoint and completes
    rc = main(["--arch", "tinyllama-1.1b", "--smoke", "--steps", "8",
               "--batch", "4", "--seq", "64", "--ckpt-dir", ckpt,
               "--ckpt-every", "2", "--resume"])
    assert rc == 0


def test_compile_cache_follows_env_else_checkout(monkeypatch, tmp_path):
    """`enable_compile_cache`: JAX_COMPILATION_CACHE_DIR, when set, is left
    to JAX (nothing configured in code); otherwise the fixed in-checkout
    directory, never a temp/pid/time-derived one."""
    import pathlib

    import jax
    from repro.launch import compile_cache as cc
    before = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv(cc.ENV_VAR, str(tmp_path))
        assert cc.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv(cc.ENV_VAR)
        assert cc.enable_compile_cache() == str(cc.CHECKOUT_CACHE)
        assert jax.config.jax_compilation_cache_dir == str(cc.CHECKOUT_CACHE)
        root = pathlib.Path(__file__).resolve().parents[1]
        assert cc.CHECKOUT_CACHE == root / ".jax_cache"
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_serve_driver_continuous_reports_every_fallback_counter(capsys):
    """The continuous engine's summary line carries all three kernel
    fallback counters and the supervision counters."""
    from repro.launch.serve import main
    rc = main(["--arch", "qwen3-0.6b", "--smoke", "--engine", "continuous",
               "--batch", "2", "--prompt-len", "32", "--gen", "4",
               "--prefill-chunk", "16", "--sample-device", "fused"])
    assert rc == 0
    out = capsys.readouterr().out
    for key in ("prefill_kernel_fallbacks=0", "paged_kernel_fallbacks=0",
                "finalize_kernel_fallbacks=0", "retries=0",
                "degradation_level=0"):
        assert key in out, key


def test_serve_driver_end_to_end(capsys):
    from repro.launch.serve import main
    rc = main(["--arch", "qwen3-0.6b", "--smoke", "--batch", "2",
               "--prompt-len", "64", "--gen", "8"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "decode:" in out and "tok/s" in out


def test_training_reduces_loss():
    """A small MiTA transformer must actually learn the synthetic stream."""
    import jax
    from repro.configs.registry import ShapeSpec, get_arch
    from repro.data import DataConfig, synthetic_batch
    from repro.launch.mesh import make_host_mesh
    from repro.launch.steps import build_cell, family_fns
    from repro.optim import OptConfig, adamw_init

    arch = get_arch("tinyllama-1.1b", smoke=True)
    mesh = make_host_mesh(1, 1)
    shape = ShapeSpec("t", "train", 64, 8)
    cell = build_cell(arch, shape, mesh,
                      opt_cfg=OptConfig(lr=1e-3, warmup_steps=2,
                                        total_steps=40))
    fns = family_fns(arch)
    with mesh:
        params = fns["init"](jax.random.PRNGKey(0))
        opt = adamw_init(params)
        step = jax.jit(cell.fn)
        dcfg = DataConfig(vocab=arch.model.vocab, seq_len=64, global_batch=8)
        losses = []
        for i in range(30):
            b = synthetic_batch(dcfg, i)
            params, opt, m = step(params, opt, b)
            losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.3, losses[::6]
