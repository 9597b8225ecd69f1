"""The readings that a cell's limits are set from, at the cell's own size, on
the chip, several seeds in one process (set-up is most of a run):

    python3 benchmark/tests/control_on_chip.py --workload <cell> \
        --seeds 11,12,13 --seconds 8 --control fp8 --out chiprun_out/ctl.jsonl

For each seed it drives the cell as run.py does (same runner, same window
code) and has the runner read, beside the program's numbers, the control's:
the reference put in the program's place, computed one step of precision below
the configuration's (fp8 for bfloat16), and for a training cell the planted
fault "half of the batch left out". One JSON line per seed. The benchmark's own
runs never run the control. `--control none` reads the program's numbers only.
"""
from __future__ import annotations

import argparse
import gc
import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run as harness  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--control", default="fp8")
    ap.add_argument("--out", default=None)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args()
    import jax
    if not args.rehearsal and jax.devices()[0].platform != "tpu":
        print("control_on_chip.py: no TPU", file=sys.stderr)
        return 3
    loaded = harness.load_cell(args.workload)
    runner = importlib.import_module(
        f"benchmark.runners.{loaded['config']['runner']}")
    for seed in (int(s) for s in args.seeds.split(",")):
        ns = argparse.Namespace(seed=seed, seconds=args.seconds, trace=0,
                                rehearsal=args.rehearsal)
        ctx = harness.Context(ns, loaded, jax)
        ctx.control = None if args.control == "none" else args.control
        res = runner.run(ctx)
        line = {"workload": args.workload, "seed": seed, "program": res["compared"],
                "faults": res["faults"],
                "readings": res["observed"].get("readings", {}),
                "end_to_end": None if args.rehearsal else res["end_to_end"],
                "memory_peak_bytes": ctx.memory_peak_bytes}
        print(json.dumps(line), flush=True)
        if args.out:
            os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(line) + "\n")
        del res, ctx
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
