"""Checks of the benchmark that need no device: the traffic generator,
the FLOP and byte counts, the configuration files against the registry,
BENCHMARK.json against the files it names, and the harness's refusal to
run without a TPU."""

import json
import math
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from bench import flops, model
from bench.traffic import Traffic

ROOT = Path(__file__).resolve().parents[2]
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

SPEC = {"hidden_size": 8, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 2, "intermediate_size": 16,
        "vocab_size": 10, "num_hidden_layers": 3,
        "mita": {"window": 4, "expert_width": 3}}


def test_matmul_and_attention_counts_by_hand():
    # q 8x8, k 8x4, v 8x4, o 8x8, mlp 3 x 8x16
    assert flops.layer_matmul_params(SPEC) == 64 + 32 + 32 + 64 + 384
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


# ------------------------------------------------------------ configs --

REGISTRY_KEYS = {"hidden_size": "d_model", "intermediate_size": "d_ff",
                 "num_hidden_layers": "n_layers",
                 "num_attention_heads": "n_heads",
                 "num_key_value_heads": "n_kv", "head_dim": "head_dim",
                 "vocab_size": "vocab", "rope_theta": "rope_theta",
                 "tie_word_embeddings": "tie_embeddings"}


CONFIGS = sorted(p.stem for p in (ROOT / "bench/configs").glob("*.json"))


@pytest.mark.parametrize("name", CONFIGS)
def test_config_widths_equal_the_registry(name):
    from repro.configs.registry import get_arch

    spec = model.load_config(name)
    reduced = spec.get("reduced", [])
    for entry in BENCH["configs"]:
        if entry["name"] == name:
            assert entry["reduced"] == reduced
    reg = get_arch(spec["registry"]).model
    for key, attr in REGISTRY_KEYS.items():
        if key not in reduced:
            assert spec[key] == getattr(reg, attr), key
    assert spec["mita"]["window"] == reg.attn.window
    assert spec["mita"]["expert_width"] == reg.attn.k
    assert spec["mita"]["routed_experts"] == reg.attn.s
    for key in reduced:
        assert spec[key] != getattr(reg, REGISTRY_KEYS[key])
        assert spec["published"][key] == getattr(reg, REGISTRY_KEYS[key])
    cfg = model.model_config(spec)
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv, cfg.dh, cfg.d_ff) == (
        reg.d_model, reg.n_heads, reg.n_kv, reg.dh, reg.d_ff)


# ------------------------------------------------------ BENCHMARK.json --

def test_benchmark_names_only_files_that_exist():
    metrics = {m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]}
    for name in metrics:
        assert (ROOT / "bench/metrics" / f"{name}.py").is_file(), name
    for c in BENCH["configs"]:
        spec = json.loads((ROOT / c["file"]).read_text())
        assert spec["name"] == c["name"]
        assert (ROOT / "bench/configs" / f"{spec['reference']}.py").is_file()
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
