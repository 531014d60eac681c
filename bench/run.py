#!/usr/bin/env python3
"""Run one benchmark cell once on the chip this process finds.

    python3 bench/run.py --workload qwen3-32b-pp16.longdoc-closed --seed 7 \
        --seconds 30 --trace 0

The cell is an entry of ``workloads`` in BENCHMARK.json: a configuration
(``bench/configs/<config>.json``, with the plain reference it names), a
traffic mix (``bench/traffic/<traffic>.json``) and the cell's own sizes
and limits (``bench/cells/<workload>.json``).  Every metric is a reader in
``bench/metrics/<name>.py``.  Adding a cell, a configuration, a traffic
mix or a metric adds files and entries; nothing here changes.

In order: the compile cache is enabled; the weights are made on the
device from the seed; the engine (`Supervisor` over `ServingEngine`, the
backend from `serve.backends.for_arch`: 512-token batched chunked
prefill, fused sampling, external finalize) compiles or loads each program
the cell's traffic uses by serving probe requests; the traffic brings it
to steady occupancy; the window is measured for ``--seconds``; then the
reference checks a sample of what the window served.  With ``--trace 1``
the last seconds of the window are traced and the per-layer metrics are
reported instead of the end-to-end ones.

Exits nonzero, printing no result line, when JAX finds no TPU or fewer
chips than the cell asks for, or when the repository's sources are not
beside this directory.  Otherwise the last line of stdout is one JSON
object with ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` (and ``breakdown`` when traced), and last ``checks``: each
number compared with its limit, which also close standard error.
"""

from __future__ import annotations

import time

T_PROC = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
OUT = ROOT / "bench_out"
TRACE_S = 15.0          # traced: the window's last seconds


class NoChip(Exception):
    """No accelerator, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def find_cell(bench: dict, workload: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == workload:
            return w
    raise SystemExit(f"bench/run.py: no workload {workload!r} in "
                     "BENCHMARK.json")


def cell_metrics(bench: dict, workload: str, kind: str) -> list:
    return [m for m in bench[kind]
            if workload in m.get("workloads", [workload])]


def devices(chips: int, require_tpu: bool = True):
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU (JAX found {devs[0].platform})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs


COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


def _compile_counter():
    """Counts compilations and compile-cache loads while ``on``."""
    import jax

    state = {"on": False, "n": 0}

    def listener(event: str, *_a, **_k):
        if state["on"] and event in COMPILE_EVENTS:
            state["n"] += 1

    jax.monitoring.register_event_duration_secs_listener(listener)
    return state


def passes(checks: dict) -> bool:
    """Every number compared is within its limit."""
    return all(c["value"] <= c["limit"] for c in checks.values())


def warm_up(sup, slots: int, length: int) -> None:
    """Serve probe requests at every batched-prefill row width the engine
    can use, so each program the window drives is compiled (or loaded
    from the cache) now, on this engine."""
    import numpy as np
    from repro.serve import Request

    from bench.model import prefill_widths

    rid = -1
    for k in prefill_widths(slots):
        for _ in range(k):
            sup.submit(Request(rid=rid, prompt=np.arange(length,
                                                         dtype=np.int32),
                               max_new_tokens=2))
            rid -= 1
        while sup.step():
            pass


def run_cell(workload: str, seed: int, seconds: float, traced: bool,
             control: bool = False, root: Path = ROOT,
             require_tpu: bool = True, t_proc: float = T_PROC,
             fault=None) -> dict:
    """One run of one cell.  ``control``: also put the float8 control in
    the program's place on the same sample and judge it by the same
    checks.  ``fault``: a callable given the engine before traffic starts
    (the tests use it to break the timed path)."""
    import jax
    import numpy as np

    from bench import check, model
    from bench import trace as trace_mod
    from bench.driver import CLOCK, SPANS, Driver
    from bench.traffic import Traffic
    from bench.window import RunData, reader

    bench = load_benchmark(root)
    bdir = root / "bench"
    cell = find_cell(bench, workload)
    devs = devices(cell["chips"], require_tpu)
    if require_tpu:
        from repro.launch.compile_cache import enable_compile_cache
        log(f"compile cache: {enable_compile_cache()}")
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    kind = devs[0].device_kind
    peaks = json.loads((bdir / "peaks.json").read_text())
    if require_tpu and kind not in peaks:
        raise SystemExit(f"bench/run.py: no peaks for device kind {kind!r}"
                         " in bench/peaks.json")
    peak = peaks.get(kind, next(iter(peaks.values())))

    spec = model.load_config(cell["config"], bdir)
    tspec = model.load_json("traffic", cell["traffic"], bdir)
    sizes = model.load_json("cells", workload, bdir)
    traffic = Traffic(tspec, seed, spec["vocab_size"])
    slots = int(sizes["slots"])

    from repro.serve import ServingEngine, Supervisor, SupervisorConfig
    from repro.serve.backends import for_arch

    params = model.make_params(spec, seed)
    jax.block_until_ready(params)
    arch = model.arch_config(spec)
    w = arch.model.attn.window
    ecfg = model.engine_config(slots, -(-traffic.max_context() // w),
                               int(sizes["pool_tokens"]) // w)
    eng = ServingEngine(params, arch.model, ecfg,
                        backend=for_arch(arch, params, ecfg))
    sup = Supervisor(eng, SupervisorConfig())
    warm_up(sup, slots, min(ecfg.prefill_chunk,
                            int(tspec["prompt"]["min"])))
    base = eng.stats()
    if fault is not None:
        fault(eng)
    compiles = _compile_counter()

    drv = Driver(sup, traffic, slots)
    warm_s = float(tspec["warm_s"])
    drv.start(warm_s + seconds + 60)
    if drv.open:
        t_w = drv.t_start + warm_s
        drv.run_until(lambda: CLOCK() >= t_w)
    else:
        # every slot holds a request, and every client's first request
        # (rids 0 .. slots-1) has finished its prefill
        first = set(range(slots))

        def filled() -> bool:
            pending = {j.entry.req.rid for j in eng.prefilling.values()}
            pending |= {e.req.rid for e in eng.waiting}
            return (len(eng.prefilling) + len(eng.slot_req) == slots
                    and not pending & first)

        t_fill = CLOCK() + 120
        drv.run_until(lambda: CLOCK() >= t_fill or filled())
        t_w = CLOCK() + warm_s
        drv.run_until(lambda: CLOCK() >= t_w)

    # ------------------------------------------------ measured window --
    # the window opens and closes between passes of the drive loop: it
    # ends with the first pass that ends past ``seconds``, so every
    # dispatch lies wholly inside or outside it
    w0 = CLOCK()
    w1 = w0 + seconds
    n_steps0 = len(eng.step_times)
    backlog0 = len(eng.waiting) + len(eng.prefilling)
    compiles["on"] = True
    t0_tr = t1_tr = math.nan
    tdir = OUT / "trace"
    if traced:
        t_tr = max(w0, w1 - TRACE_S)
        drv.run_until(lambda: CLOCK() >= t_tr)
        shutil.rmtree(tdir, ignore_errors=True)
        jax.profiler.start_trace(str(tdir))
        t0_tr = CLOCK()
        with jax.profiler.TraceAnnotation(trace_mod.ANCHOR):
            pass
    drv.run_until(lambda: CLOCK() >= w1)
    w1 = CLOCK()
    if traced:
        t1_tr = w1
        jax.profiler.stop_trace()
    step_times = list(eng.step_times[n_steps0:])
    compiles["on"] = False
    load = {"backlog_start": backlog0,
            "backlog_end": len(eng.waiting) + len(eng.prefilling),
            "completed": sum(1 for r in drv.recs.values()
                             if w0 <= r.finished < w1)}

    drv.settle()
    stats = eng.stats()
    # the TPU runtime keeps programs' temporary buffers apart from the
    # arrays, under "reserved"; the chip's peak is both
    mem_stats = devs[0].memory_stats() or {}
    mem = (mem_stats.get("peak_bytes_in_use", 0)
           + mem_stats.get("peak_bytes_reserved", 0))

    run = RunData(spec=spec, slots=slots, recs=drv.recs,
                  dispatches=drv.dispatches, busy=drv.busy,
                  step_times=step_times, t_proc=t_proc, w0=w0, w1=w1,
                  peak=peak)
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": int(mem)}
    result: dict = {}
    if traced:
        tr = trace_mod.load(trace_mod.find_xplane(str(tdir)), SPANS)
        run.trace, run.t0_trace, run.t1_trace = tr, t0_tr, t1_tr
        run.offset_ns = tr.anchor_ns() - t0_tr * 1e9
        lo, hi = run.trace_bounds_ns()
        device["busy_s"] = trace_mod.busy_ns(tr, lo, hi) / 1e9
        device["window_s"] = (hi - lo) / 1e9
        result["breakdown"] = trace_mod.breakdown(tr, lo, hi)
    kinds = "per_layer" if traced else "end_to_end"
    metrics = {}
    for m in cell_metrics(bench, workload, kinds):
        v = reader(m["name"], bdir / "metrics")(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    window = run.due_in_window()
    rejected = sum(1 for r in window if r.reason == "rejected")
    lag = sorted(r.submit - r.due for r in window)
    load["submit_lag_p95_ms"] = (float(np.percentile(lag, 95)) * 1e3
                                 if lag else None)
    fallbacks = sum(stats[k] - base[k] for k in (
        "prefill_kernel_fallbacks", "paged_kernel_fallbacks",
        "finalize_kernel_fallbacks"))

    # ------------------------------------------- the reference's check --
    recs = drv.recs
    del drv, sup, eng, run
    gc.collect()
    chk = tspec["check"]
    picked = check.sample(recs, w0, seed, int(chk["tokens"]),
                          int(chk["requests"]))
    ref_mod = check.reference(spec, bdir / "configs")
    t_ref = CLOCK()
    prog, ctrl, detail = [], [], []
    pad = traffic.max_context()
    for r in picked:
        t_r = CLOCK()
        g, c = check.gaps(ref_mod, params, spec, r, pad,
                          int(tspec["output"]["max"]), control=control)
        prog.append(g)
        detail.append(check.describe(r, g, c, CLOCK() - t_r))
        log(f"reference: {detail[-1]}")
        if control:
            ctrl.append(c)
    log(f"reference: {len(picked)} requests, "
        f"{sum(len(r.tokens) for r in picked)} served tokens, "
        f"{CLOCK() - t_ref:.1f} s")
    def compared(gaps) -> dict:
        """The numbers the cell's file limits, read from ``gaps``."""
        return {name: {"value": check.NUMBERS[name](gaps),
                       "limit": float(limit)}
                for name, limit in sizes["limits"].items()}

    checks = {
        **compared(prog),
        "rejected": {"value": rejected, "limit": 0},
        "kernel_fallbacks": {"value": int(fallbacks), "limit": 0},
        "degradation_level": {"value": int(stats["degradation_level"]),
                              "limit": 0},
        "compiles_in_window": {"value": compiles["n"], "limit": 0},
    }
    result = {"correct": passes(checks), "attempted": len(window),
              "failed": rejected, "metrics": metrics,
              "device": device, **result}
    if control:
        # the control in the program's place, through the same checks
        ctrl_checks = {**checks, **compared(ctrl)}
        result["control"] = {
            "correct": passes(ctrl_checks), "checks": ctrl_checks,
            "requests": detail,
            "gaps": [[np.asarray(g).tolist(), np.asarray(c).tolist()]
                     for g, c in zip(prog, ctrl)]}
    result["stats"] = {k: v for k, v in stats.items() if k != "backend"}
    result["load"] = load
    # the checks close the result line and standard error
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        log(f"bench/run.py: no repository sources at {ROOT / 'src'}")
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    try:
        res = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace))
    except NoChip as e:
        log(f"bench/run.py: {e}")
        return 3
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
