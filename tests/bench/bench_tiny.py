"""A tiny cell of the benchmark for CPU tests: a copy of ``bench/`` with a
two-layer configuration, small traffic mixes and their cells, under a
BENCHMARK.json of its own."""

from __future__ import annotations

import json
import shutil
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

CONFIG = {
    "name": "tiny", "source": "test", "reference": "qwen3_mita",
    "hidden_size": 128, "intermediate_size": 256, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "vocab_size": 251, "rope_theta": 1000000, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": True,
    "mita": {"window": 16, "expert_width": 16, "routed_experts": 1},
    # float32 compute: at this width bf16 rounding alone moves logits
    # by a good part of their spread
    "dtypes": {"params": "float32", "compute": "float32"}}

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
    (tmp / "bench/configs/tiny.json").write_text(json.dumps(CONFIG))
    mixes = {k: {**COMMON, **v} for k, v in
             {**TRAFFIC, **(traffic or {})}.items()}
    cells = []
    for name, spec in mixes.items():
        (tmp / f"bench/traffic/{name}.json").write_text(json.dumps(spec))
        (tmp / f"bench/cells/tiny.{name}.json").write_text(json.dumps(
            {"slots": 3, "pool_tokens": 4096,
             "limits": {"logit_gap_mean": LIMIT,
                        "logit_gap_median": LIMIT}}))
        cells.append({"name": f"tiny.{name}", "config": "tiny",
                      "traffic": name, "chips": 1, "why": "test"})
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny", "source": "test",
                         "file": "bench/configs/tiny.json", "reduced": [],
                         "why": "test"}]
    bench["workloads"] = cells
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


def run(root: Path, workload: str, seed: int = 2**31 + 5, **kw) -> dict:
    from bench.run import run_cell

    return run_cell(workload, seed, 3.0, False, root=root,
                    require_tpu=False, **kw)
