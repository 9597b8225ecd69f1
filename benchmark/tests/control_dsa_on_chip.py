"""The control of `index_set_miss_deep`, at the cell's own size, on the chip:

    python3 benchmark/tests/control_dsa_on_chip.py --seed 11 --seconds 20 \
        --out chiprun_out/ctl_dsa.jsonl

drives `serve.glm52.longctx_closed32` as run.py does, with one fault planted in
the PROGRAM (`runners/serve_paged_dsa.py` `plant_most_recent`: the last indexer
layer keeps each row's most recent `index_topk` positions instead of its highest
index scores) and puts what the runner compared through `lib.check.judge` with
the cell's limits. The run has to come out NOT correct, by `index_set_miss_deep`
(and by nothing the first indexer layer reads). One JSON line; exit 0 where the
limit caught the fault, 1 where it did not. `control_on_chip.py --control fp8`
is the other control of this cell.
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run as harness  # noqa: E402
from benchmark.lib import check  # noqa: E402

CELL = "serve.glm52.longctx_closed32"


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args()
    import jax
    if not args.rehearsal and jax.devices()[0].platform != "tpu":
        print("control_dsa_on_chip.py: no TPU", file=sys.stderr)
        return 3
    loaded = harness.load_cell(CELL)
    runner = importlib.import_module(
        f"benchmark.runners.{loaded['config']['runner']}")
    ns = argparse.Namespace(seed=args.seed, seconds=args.seconds, trace=0,
                            rehearsal=args.rehearsal)
    ctx = harness.Context(ns, loaded, jax)
    ctx.control = "recent_deep"
    res = runner.run(ctx)
    verdict = check.judge(res["compared"], loaded["limits"], args.rehearsal)
    caught = not verdict["compared"]["index_set_miss_deep"]["ok"]
    readings = res["observed"].get("readings", {})
    line = {"workload": CELL, "seed": args.seed, "planted": "recent_deep",
            "correct": bool(verdict["correct"] and not res["faults"]),
            "caught_by_index_set_miss_deep": caught,
            "compared": verdict["compared"], "faults": res["faults"],
            "overlap_by_layer": readings.get("overlap_by_layer"),
            "replay_tokens_same_share": readings.get("replay_tokens_same_share")}
    print(json.dumps(line), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "a") as f:
            f.write(json.dumps(line) + "\n")
    return 0 if caught else 1


if __name__ == "__main__":
    sys.exit(main())
