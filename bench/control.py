#!/usr/bin/env python3
"""Readings for a cell's logit-gap limit: the program's and the float8
control's, seed by seed, in one process on the chip this process finds.

    python3 bench/control.py --workload qwen3-32b-pp16.longdoc-closed \
        --seeds 201,202,203 --seconds 10

Each seed runs the cell as ``bench/run.py`` does, with a short window at
the cell's own load, then compares the same sample of served requests
twice: the program's served tokens against the float32 reference, and the
first choices of the reference computed with float8 (e4m3) operands in
every weight matrix product, judged by the same checks as the program
(``control_correct`` has to come out false).  One JSON line per seed.
The limit is set between the largest program reading and the smallest
control reading; the benchmark's own runs never run the control.
"""

from __future__ import annotations

import time

T_PROC = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--dump", default="",
                    help="append each seed's per-token gaps, program and "
                         "control, to this JSON-lines file")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    from bench import check
    from bench.run import NoChip, log, run_cell

    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            res = run_cell(args.workload, seed, args.seconds, False,
                           control=True, t_proc=time.perf_counter())
        except NoChip as e:
            log(f"bench/control.py: {e}")
            return 3
        gaps = res["control"]["gaps"]
        if args.dump:
            with open(args.dump, "a") as f:
                f.write(json.dumps({"seed": seed,
                                    "gaps": gaps}) + "\n")
        print(json.dumps({
            "seed": seed, "correct": res["correct"],
            "control_correct": res["control"]["correct"],
            "program": {k: f([g for g, _ in gaps])
                        for k, f in check.NUMBERS.items()},
            "control": {k: f([c for _, c in gaps])
                        for k, f in check.NUMBERS.items()},
            "requests": res["control"]["requests"],
            "metrics": {k: v["value"] for k, v in res["metrics"].items()},
            "memory_peak_bytes": res["device"]["memory_peak_bytes"],
            "load": res["load"]}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
