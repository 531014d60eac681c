"""Checks of the benchmark that need no device: the traffic generator,
the FLOP and byte counts, the model modules that configurations name,
the configuration files against the registry, BENCHMARK.json against the
files it names, and the harness's refusal to run without a TPU."""

import json
import math
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import bench_tiny
from bench import flops, model
from bench.traffic import Traffic

ROOT = Path(__file__).resolve().parents[2]
FIXTURES = ROOT / "tests/bench/fixtures"
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
TRAFFIC = sorted(p.stem for p in (ROOT / "bench/traffic").glob("*.json"))


# ------------------------------------------------------------ traffic --

@pytest.mark.parametrize("name", TRAFFIC)
def test_traffic_is_deterministic_in_range_and_whole_windows(name):
    spec = model.load_json("traffic", name)
    a, b = Traffic(spec, 2**31 + 11, 1000), Traffic(spec, 2**31 + 11, 1000)
    c = Traffic(spec, 12, 1000)
    draws = [a.draw(i) for i in range(70)]
    assert all(np.array_equal(d.prompt, b.draw(i).prompt)
               and d.max_new == b.draw(i).max_new
               for i, d in enumerate(draws))
    # another seed: the same sizes, other token ids
    assert all(len(d.prompt) == len(c.draw(i).prompt)
               and d.max_new == c.draw(i).max_new
               for i, d in enumerate(draws))
    assert not any(np.array_equal(d.prompt, c.draw(i).prompt)
                   for i, d in enumerate(draws))
    p, o = spec["prompt"], spec["output"]
    for d in draws:
        n = len(d.prompt)
        assert p["min"] <= n <= p["max"] and n % p.get("multiple", 1) == 0
        assert o["min"] <= d.max_new <= o["max"]
        assert d.max_new % o.get("multiple", 1) == 0
        assert d.prompt.dtype == np.int32
        assert 0 <= d.prompt.min() and d.prompt.max() < 1000
    if spec["loop"] == "open":
        dues = a.dues(640)
        assert np.all(np.diff(dues) > 0)
        assert np.array_equal(dues, b.dues(640))
        assert np.array_equal(dues, c.dues(640))
        assert abs(dues[-1] / 640 * spec["rate_per_s"] - 1) < 0.05
    else:
        first = [a.client_draw(k, 0, 8).max_new for k in range(8)]
        full = [a.draw(k).max_new for k in range(8)]
        assert all(1 <= f <= g for f, g in zip(first, full))


@pytest.mark.parametrize("name", TRAFFIC)
def test_every_block_holds_one_size_in_each_stratum(name):
    spec = model.load_json("traffic", name)
    p = spec["prompt"]
    lo, hi = math.log(p["min"]), math.log(p["max"])
    t = Traffic(spec, 1, 100)
    for b in range(3):
        u = sorted((math.log(len(t.draw(b * t.block + i).prompt)) - lo)
                   / (hi - lo) for i in range(t.block))
        # rounding down to the length multiple moves a draw down by at
        # most one multiple
        slack = (math.log(p["min"] + p.get("multiple", 1))
                 - math.log(p["min"])) / (hi - lo)
        for j, x in enumerate(u):
            assert j / t.block - slack <= x <= (j + 1) / t.block + 1e-9


# -------------------------------------------------------------- flops --

SPEC = {"model": "qwen3_dense", "hidden_size": 8, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 2, "intermediate_size": 16,
        "vocab_size": 10, "num_hidden_layers": 3,
        "mita": {"window": 4, "expert_width": 3}}


def test_matmul_and_attention_counts_by_hand():
    # q 8x8, k 8x4, v 8x4, o 8x8, mlp 3 x 8x16
    assert model.module(SPEC).layer_matmul_params(SPEC) == \
        64 + 32 + 32 + 64 + 384
    # prompt position 9, window 4: windows 0, 1 closed (ends 4, 8);
    # 2 landmarks + 3 expert rows + local positions 8, 9
    assert flops.attend_flops(SPEC, 9, prompt=True) == 4 * 4 * 2 * (2 + 3 + 2)
    # generated position 8 sees windows ending <= 8: 2 landmarks
    assert flops.attend_flops(SPEC, 8, prompt=False) == 4 * 4 * 2 * (2 + 3 + 1)
    # first window: no landmark, no expert
    assert flops.attend_flops(SPEC, 2, prompt=True) == 4 * 4 * 2 * 3
    # position 11 closes window 2: 2 KV heads score 12 keys, sum 12 values
    assert flops.landmark_flops(SPEC, 11) == 2 * 4 * 2 * 12
    assert flops.landmark_flops(SPEC, 10) == 0
    tok = flops.token_flops(SPEC, 9, prompt=True, head=True)
    assert tok == 3 * (2 * 576 + 224) + 2 * 80


def test_paged_decode_counts_by_hand():
    ops, nbytes = flops.paged_decode(SPEC, [9])
    # position 9 (generated): 2 landmarks, 3 expert rows, local 8 (read)
    assert ops == 3 * flops.attend_flops(SPEC, 9, prompt=False)
    per_layer = 2 * 2 * (2 * (2 * 2 + 2 * (3 + 1) + 2) + 2 * 4)
    assert nbytes == 3 * per_layer


def test_chunk_prefill_counts_by_hand():
    ops, nbytes = flops.chunk_prefill(SPEC, [(4, 4)])
    want = sum(flops.attend_flops(SPEC, p, prompt=True)
               + flops.landmark_flops(SPEC, p) for p in range(4, 8))
    assert ops == 3 * want
    # context K and V before and in the chunk (8 rows) per KV head, plus
    # the chunk's queries read and outputs written per query head
    assert nbytes == 3 * 2 * 2 * (2 * 2 * 8 + 2 * 4 * 4)


def test_roofline_takes_the_larger_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.roofline_seconds(100, 5, peak) == 1.0
    assert flops.roofline_seconds(10, 50, peak) == 5.0


def test_routed_expert_counts_by_hand():
    """The fixture's routed-expert module counts the K experts a token is
    routed to, the shared expert and the router, not every expert."""
    spec = {**SPEC, "model": "qwen3_moe", "num_experts": 8,
            "num_experts_per_tok": 2, "num_shared_experts": 1,
            "moe_intermediate_size": 6,
            model.CONFIGS_DIR: str(FIXTURES / "configs")}
    mod = model.module(spec)
    # attention 192; router 8x8; shared + 2 routed experts, 3 x 8x6 each
    assert mod.layer_matmul_params(spec) == 192 + 64 + 3 * 3 * 48
    assert flops.token_flops(spec, 9, prompt=True, head=True) == \
        3 * (2 * 688 + 224) + 2 * 80
    assert flops.paged_decode(spec, [9]) == flops.paged_decode(SPEC, [9])


# ------------------------------------------------ pinned numbers --
# Counts, weight shapes and weights of the dense Qwen3 configurations:
# a change to the harness may move none of them.

def test_qwen3_32b_counts_are_pinned():
    spec = model.load_config("qwen3-32b-pp16")
    assert model.module(spec).layer_matmul_params(spec) == 487587840
    assert flops.token_flops(spec, 4095, prompt=False, head=True) == \
        4199907328
    assert flops.token_flops(spec, 8191, prompt=True, head=False) == \
        4076863488
    assert flops.paged_decode(spec, [1000, 4095]) == (69074944, 8896512)
    assert flops.chunk_prefill(spec, [(1536, 512)]) == (13946585088,
                                                        100663296)


PARAM_SHAPES = {
    "qwen3-0.6b": {
        "emb.tok": ((151936, 1024), 0.02),
        "blocks.ln1": ((28, 1024), 0.1),
        "blocks.ln2": ((28, 1024), 0.1),
        "blocks.attn.wq": ((28, 1024, 2048), 0.03125),
        "blocks.attn.wk": ((28, 1024, 1024), 0.03125),
        "blocks.attn.wv": ((28, 1024, 1024), 0.03125),
        "blocks.attn.wo": ((28, 2048, 1024), 0.0029528474453845875),
        "blocks.attn.q_norm": ((28, 128), 0.1),
        "blocks.attn.k_norm": ((28, 128), 0.1),
        "blocks.ffn.wi": ((28, 1024, 3072), 0.03125),
        "blocks.ffn.wg": ((28, 1024, 3072), 0.03125),
        "blocks.ffn.wo": ((28, 3072, 1024), 0.0024109898431576866),
        "ln_f": ((1024,), 0.1),
    },
    "qwen3-32b-pp16": {
        "emb.tok": ((18992, 5120), 0.02),
        "blocks.ln1": ((4, 5120), 0.1),
        "blocks.ln2": ((4, 5120), 0.1),
        "blocks.attn.wq": ((4, 5120, 8192), 0.013975424859373685),
        "blocks.attn.wk": ((4, 5120, 1024), 0.013975424859373685),
        "blocks.attn.wv": ((4, 5120, 1024), 0.013975424859373685),
        "blocks.attn.wo": ((4, 8192, 5120), 0.0039062499999999996),
        "blocks.attn.q_norm": ((4, 128), 0.1),
        "blocks.attn.k_norm": ((4, 128), 0.1),
        "blocks.ffn.wi": ((4, 5120, 25600), 0.013975424859373685),
        "blocks.ffn.wg": ((4, 5120, 25600), 0.013975424859373685),
        "blocks.ffn.wo": ((4, 25600, 5120), 0.002209708691207961),
        "ln_f": ((5120,), 0.1),
        "emb.head": ((5120, 18992), 0.013975424859373685),
    },
}


@pytest.mark.parametrize("name", sorted(PARAM_SHAPES))
def test_param_shapes_are_pinned(name):
    got = model.param_shapes(model.load_config(name))
    assert {k: (tuple(shape), float(scale))
            for k, (shape, scale) in got.items()} == PARAM_SHAPES[name]


# per leaf of the tiny configuration's weights for seed 2**31 + 5: the
# float64 sum and sum of squares.  Sums rather than a hash of the bytes:
# the normal draw's float rounding may differ by an ulp between CPUs,
# which moves these by under 1e-7 of their size; another key, order,
# scale or dtype moves them by far more.
TINY_WEIGHTS = {
    "blocks.attn.k_norm": (-1.08542257564568, 0.5037923293119206),
    "blocks.attn.q_norm": (-0.0052458454156294465, 0.6115886797130345),
    "blocks.attn.wk": (-3.0112801290224525, 125.79598700047615),
    "blocks.attn.wo": (-5.490147806261348, 65.19073420522297),
    "blocks.attn.wq": (-13.129868534092225, 257.30821766838636),
    "blocks.attn.wv": (-9.201217141298912, 129.2911963153406),
    "blocks.ffn.wg": (-66.83673686592442, 510.35002841549976),
    "blocks.ffn.wi": (-3.533263464949755, 514.7982409156707),
    "blocks.ffn.wo": (-2.1121302089511573, 63.73636630119681),
    "blocks.ln1": (0.46069269557483494, 2.191936881608525),
    "blocks.ln2": (-2.1918548659887165, 2.529928337079216),
    "emb.tok": (1.7166127845228516, 12.783384355755327),
    "ln_f": (1.107619698275812, 1.2522594168913175),
}


def test_tiny_weights_are_pinned():
    import jax

    params = model.make_params(bench_tiny.CONFIG, 2**31 + 5)
    got = {}
    for path, a in jax.tree_util.tree_flatten_with_path(params)[0]:
        assert a.dtype == np.float32
        a = np.asarray(a, np.float64)
        got[".".join(p.key for p in path)] = (a.sum(), (a * a).sum())
    assert set(got) == set(TINY_WEIGHTS)
    for name, want in TINY_WEIGHTS.items():
        np.testing.assert_allclose(got[name], want, rtol=1e-6, err_msg=name)


def test_per_weight_dtype_is_honoured():
    """The routed-expert module keeps its router in float32 while the
    configuration serves bfloat16 weights."""
    import jax

    spec = {**bench_tiny.MOE_CONFIG,
            "dtypes": {"params": "bfloat16", "compute": "bfloat16"},
            model.CONFIGS_DIR: str(FIXTURES / "configs")}
    tree = jax.eval_shape(lambda k: model.init_params(spec, k),
                          jax.random.PRNGKey(0))
    moe = tree["blocks"]["moe"]
    assert moe["router"].dtype == np.float32
    assert moe["router"].shape == (2, 128, 8)
    assert moe["wi"].shape == (2, 8, 128, 64)
    assert moe["wi"].dtype == moe["shared"]["wo"].dtype == jax.numpy.bfloat16
    assert "ffn" not in tree["blocks"]


@pytest.mark.parametrize("change,error,words", [
    ({"model": None}, ValueError, "names no model module"),
    ({"model": "no_such_module"}, FileNotFoundError, "no_such_module.py"),
], ids=["no-model", "missing-module"])
def test_config_without_its_model_module_fails_clearly(change, error, words):
    spec = {k: v for k, v in {**bench_tiny.CONFIG, **change}.items()
            if v is not None}
    for call in (model.model_config, model.param_shapes,
                 lambda s: flops.token_flops(s, 1, prompt=True, head=False)):
        with pytest.raises(error, match=words) as info:
            call(spec)
        assert "'tiny'" in str(info.value)


# ------------------------------------------------------------ configs --

def _configs():
    """(directory holding configs/, name) of every configuration file the
    benchmark keeps, and of the fixtures' (a routed-expert module's key
    map)."""
    out = []
    for bench_dir, prefix in ((ROOT / "bench", ""), (FIXTURES, "fixture-")):
        out += [pytest.param(bench_dir, p.stem, id=prefix + p.stem)
                for p in sorted((bench_dir / "configs").glob("*.json"))]
    return out


@pytest.mark.parametrize("bench_dir,name", _configs())
def test_config_widths_equal_the_registry(bench_dir, name):
    from repro.configs.registry import get_arch

    spec = model.load_config(name, bench_dir)
    keys = model.module(spec).REGISTRY_KEYS
    reduced = spec.get("reduced", [])
    for entry in BENCH["configs"]:
        if entry["name"] == name:
            assert entry["reduced"] == reduced
    reg = get_arch(spec["registry"]).model
    for key, attr in keys.items():
        if key not in reduced:
            assert spec[key] == getattr(reg, attr), key
    assert spec["mita"]["window"] == reg.attn.window
    assert spec["mita"]["expert_width"] == reg.attn.k
    assert spec["mita"]["routed_experts"] == reg.attn.s
    for key in reduced:
        assert spec[key] != getattr(reg, keys[key])
        assert spec["published"][key] == getattr(reg, keys[key])
    cfg = model.model_config(spec)
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv, cfg.dh, cfg.d_ff) == (
        reg.d_model, reg.n_heads, reg.n_kv, reg.dh, reg.d_ff)
    assert model.arch_config(spec).family == get_arch(spec["registry"]).family


# ------------------------------------------------------ BENCHMARK.json --

def test_benchmark_names_only_files_that_exist():
    metrics = {m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]}
    for name in metrics:
        assert (ROOT / "bench/metrics" / f"{name}.py").is_file(), name
    for c in BENCH["configs"]:
        spec = json.loads((ROOT / c["file"]).read_text())
        assert spec["name"] == c["name"]
        for module in (spec["reference"], spec["model"]):
            assert (ROOT / "bench/configs" / f"{module}.py").is_file()
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for w in BENCH["workloads"]:
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        assert (ROOT / f"bench/traffic/{w['traffic']}.json").is_file()
        assert (ROOT / f"bench/cells/{w['name']}.json").is_file()
        assert len(w["why"]) <= 200
    cells = [w["name"] for w in BENCH["workloads"]]
    for m in BENCH["per_layer"]:
        for cell in m.get("workloads", cells):
            assert cell in e2e[m["moves"]].get("workloads", [cell])


# -------------------------------------------------------- no chip, no run

def _run(cwd, env_extra=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_run_exits_nonzero_without_a_tpu():
    p = _run(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_run_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for d in BENCH["paths"]:
        shutil.copytree(ROOT / d, tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
