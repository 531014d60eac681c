"""The harness end to end on the CPU, at a tiny size: both loops run and
come out correct, a traffic file dropped into bench/traffic/ is found
without any edit, a routed-expert configuration added as files alone
serves correct and is counted by its own model module, and the
comparison that decides ``correct`` fails a token altered where it is
produced, a decode step that leaves its state unchanged, and the float8
control."""

import importlib.util

import numpy as np
import pytest

import bench_tiny
from bench import window


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    extra = {"tiny-new": {"loop": "open", "rate_per_s": 4,
                          "prompt": {"min": 48, "max": 96, "multiple": 16}}}
    return bench_tiny.make(tmp_path_factory.mktemp("bench"), extra)


@pytest.mark.parametrize("workload", ["tiny.tiny-open", "tiny.tiny-closed",
                                      "tiny.tiny-long", "tiny.tiny-new"])
def test_cell_runs_correct(root, workload):
    res = bench_tiny.run(root, workload)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["load"]["submit_lag_p95_ms"] >= 0
    assert set(res["metrics"]) == {"prompt_tok_s", "output_tok_s",
                                   "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert list(res)[-1] == "checks"
    # float32 compute through the XLA paths: the served tokens are the
    # reference's own first choices
    assert res["checks"]["logit_gap_mean"]["value"] == 0.0
    assert res["checks"]["logit_gap_median"]["value"] == 0.0


def test_routed_expert_config_runs_correct_as_files(root, monkeypatch):
    """``tiny-moe`` (8 experts, top-2, one shared expert) is its config,
    model module and reference module in bench/configs/ and one cell: it
    serves through the program's MoE layer and the reference's token by
    token expert loop agrees, and ``step_mfu`` counts its operations with
    that module's ``token_flops`` over the run's dispatches."""
    seen = {}
    read = window.reader

    def capture(name, metrics_dir=window.METRICS):
        fn = read(name, metrics_dir)

        def keep(run):
            seen["run"] = run
            return fn(run)
        return keep

    monkeypatch.setattr(window, "reader", capture)
    res = bench_tiny.run(root, bench_tiny.MOE_CELL)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert all(m["value"] > 0 for m in res["metrics"].values())

    run = seen["run"]
    path = root / "bench/configs/qwen3_moe.py"
    mspec = importlib.util.spec_from_file_location("moe_counts", path)
    mod = importlib.util.module_from_spec(mspec)
    mspec.loader.exec_module(mod)
    ops = sum(mod.token_flops(run.spec, int(p), prompt=False, head=True)
              for d in run.window_dispatches("decode") for p in d.positions)
    ops += sum(mod.token_flops(run.spec, p, prompt=True, head=False)
               for d in run.window_dispatches("prefill")
               for t0, n in d.rows for p in range(int(t0), int(t0 + n)))
    assert ops > 0
    got = read("step_mfu", root / "bench/metrics")(run)
    assert got == 100.0 * ops / (run.seconds * run.peak["bf16_flops_per_s"])


@pytest.mark.parametrize("every", [5, 1])
def test_altered_token_is_not_correct(root, every):
    """Every 5th decode step (or every one) hands each slot a different
    token than the program produced: the mean gap fails the first, both
    the mean and the median fail the second."""
    def fault(eng):
        be = eng.backend
        decode = be.decode_step
        n = [0]

        def altered(tokens_in, t, active, *a, **k):
            out = np.array(decode(tokens_in, t, active, *a, **k))
            n[0] += 1
            if n[0] % every == 0:
                out = (out + 1) % bench_tiny.CONFIG["vocab_size"]
            return out
        be.decode_step = altered

    res = bench_tiny.run(root, "tiny.tiny-closed", fault=fault)
    assert not res["correct"]
    failed = {k for k, c in res["checks"].items() if c["value"] > c["limit"]}
    assert failed == ({"logit_gap_mean"} if every > 1 else
                      {"logit_gap_mean", "logit_gap_median"})


def test_decode_state_left_unchanged_is_not_correct(root):
    """The decode step hands back the cache it was given: no token's keys
    and values are kept past its prefill."""
    import jax
    import jax.numpy as jnp

    def fault(eng):
        be = eng.backend
        decode = be._decode

        def stale(p, st, *a):
            kept = jax.tree.map(jnp.copy, st)
            out = decode(p, st, *a)
            return (out[0], kept, *out[2:])
        be._decode = stale

    res = bench_tiny.run(root, "tiny.tiny-long", fault=fault)
    assert not res["correct"]
    assert res["checks"]["logit_gap_mean"]["value"] > bench_tiny.LIMIT


def test_float8_control_reads_above_the_limit(root):
    res = bench_tiny.run(root, "tiny.tiny-open", control=True)
    assert res["correct"], res["checks"]
    # at this size float8 keeps most first choices (median 0): the mean
    # is the number it fails
    assert not res["control"]["correct"], res["control"]["checks"]
    ctrl = res["control"]["checks"]["logit_gap_mean"]["value"]
    assert ctrl > bench_tiny.LIMIT
    assert ctrl >= 3 * res["checks"]["logit_gap_mean"]["value"]
