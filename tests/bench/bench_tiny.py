"""A tiny cell of the benchmark for CPU tests: a copy of ``bench/`` with a
two-layer configuration, small traffic mixes and their cells, under a
BENCHMARK.json of its own; and a tiny routed-expert configuration added as
files alone: its config, its model module and its reference module
(``fixtures/configs/``), and one cell."""

from __future__ import annotations

import json
import shutil
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
FIXTURES = Path(__file__).resolve().parent / "fixtures"

CONFIG = {
    "name": "tiny", "source": "test", "reference": "qwen3_mita",
    "model": "qwen3_dense",
    "hidden_size": 128, "intermediate_size": 256, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "vocab_size": 251, "rope_theta": 1000000, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": True,
    "mita": {"window": 16, "expert_width": 16, "routed_experts": 1},
    # float32 compute: at this width bf16 rounding alone moves logits
    # by a good part of their spread
    "dtypes": {"params": "float32", "compute": "float32"}}

# 8 experts, top-2, one shared expert; dropless routing (the module sets
# the program's capacity factor to E / K)
MOE_CONFIG = {
    **{k: v for k, v in CONFIG.items() if k != "intermediate_size"},
    "name": "tiny-moe", "reference": "qwen3_moe_mita", "model": "qwen3_moe",
    "moe_intermediate_size": 64, "num_experts": 8, "num_experts_per_tok": 2,
    "num_shared_experts": 1}
MOE_CELL = "tiny-moe.tiny-closed"

TRAFFIC = {
    "tiny-open": {"loop": "open", "rate_per_s": 6},
    "tiny-closed": {"loop": "closed", "stagger": True},
    # prompts of two and three 512-token chunks: prefill resumes across
    # chunks, and decode crosses window boundaries
    "tiny-long": {"loop": "closed", "stagger": True,
                  "prompt": {"min": 528, "max": 1200, "multiple": 16},
                  "output": {"min": 20, "max": 40}},
}
COMMON = {"prompt": {"min": 32, "max": 160, "multiple": 16},
          "output": {"min": 6, "max": 20}, "block": 8, "warm_s": 1,
          "check": {"tokens": 60, "requests": 3}}
LIMIT = 0.01


def make(tmp: Path, traffic: dict | None = None) -> Path:
    """A root holding BENCHMARK.json and bench/ with the tiny cells (and
    ``traffic``: extra traffic files, name -> parameters)."""
    shutil.copytree(ROOT / "bench", tmp / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for path in (FIXTURES / "configs").glob("*.py"):
        shutil.copy(path, tmp / "bench/configs")
    for spec in (CONFIG, MOE_CONFIG):
        (tmp / f"bench/configs/{spec['name']}.json").write_text(
            json.dumps(spec))
    mixes = {k: {**COMMON, **v} for k, v in
             {**TRAFFIC, **(traffic or {})}.items()}
    cells = []
    for name, spec in mixes.items():
        (tmp / f"bench/traffic/{name}.json").write_text(json.dumps(spec))
    pairs = [("tiny", name) for name in mixes]
    pairs.append(tuple(MOE_CELL.split(".")))
    for config, name in pairs:
        (tmp / f"bench/cells/{config}.{name}.json").write_text(json.dumps(
            {"slots": 3, "pool_tokens": 4096,
             "limits": {"logit_gap_mean": LIMIT,
                        "logit_gap_median": LIMIT}}))
        cells.append({"name": f"{config}.{name}", "config": config,
                      "traffic": name, "chips": 1, "why": "test"})
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": c, "source": "test",
                         "file": f"bench/configs/{c}.json", "reduced": [],
                         "why": "test"} for c in ("tiny", "tiny-moe")]
    bench["workloads"] = cells
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


def run(root: Path, workload: str, seed: int = 2**31 + 5, **kw) -> dict:
    from bench.run import run_cell

    return run_cell(workload, seed, 3.0, False, root=root,
                    require_tpu=False, **kw)
