"""The controls of `serve.solar2.reason_closed64`, at the cell's own size, on the
chip:

    python3 benchmark/tests/control_kda_on_chip.py --seed 11 --seconds 20 \
        --control state_bf16 --out chiprun_out/ctl_kda.jsonl

drives the cell as run.py does with `ctx.control` set, so that the runner's
reference makes a second pass one precision below the configuration's and the
line holds what that pass reads beside what the program reads:

- `fp8`: every matmul operand of the reference but the router's rounded to e4m3
  (one step below the bfloat16 of the weights and activations);
- `state_bf16`: the reference with the recurrent state rounded to bfloat16 after
  every token (one step below the float32 the configuration states for it), and
  the runner's `recurrence_probe` run a second time with a bfloat16 pool of the
  engine's shape: the control's `recurrence_gap` is the same measure as the
  program's, on the same rows, one precision below.

Each control has to come out NOT correct by at least one of the cell's limits
(`lib.check.judge` on the control's numbers), while the program itself is
correct. One JSON line; exit 0 where the limits told them apart, 1 where not.
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

CELL = "serve.solar2.reason_closed64"


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--control", choices=("fp8", "state_bf16"), required=True)
    ap.add_argument("--out", default=None)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args()
    import jax
    if not args.rehearsal and jax.devices()[0].platform != "tpu":
        print("control_kda_on_chip.py: no TPU", file=sys.stderr)
        return 3
    loaded = harness.load_cell(CELL)
    runner = importlib.import_module(
        f"benchmark.runners.{loaded['config']['runner']}")
    ns = argparse.Namespace(seed=args.seed, seconds=args.seconds, trace=0,
                            rehearsal=args.rehearsal)
    ctx = harness.Context(ns, loaded, jax)
    ctx.control = args.control
    res = runner.run(ctx)
    verdict = check.judge(res["compared"], loaded["limits"], args.rehearsal)
    readings = res["observed"].get("readings", {})
    control = readings.get("control") or {}
    # a control is judged by the numbers it reads (the fp8 control reads no
    # recurrence_gap: it rounds matmul operands, and the recurrence has none)
    limits = loaded["limits"]["rehearsal_limits" if args.rehearsal else "limits"]
    as_compared = {k: control[k] for k in res["compared"]
                   if control.get(k) is not None}
    control_verdict = check.judge(
        as_compared, {"limits": {k: limits[k] for k in as_compared}})
    line = {"workload": CELL, "seed": args.seed, "control": args.control,
            "program_correct": bool(verdict["correct"] and not res["faults"]),
            "control_correct": bool(control_verdict["correct"]),
            "compared": verdict["compared"],
            "control_compared": control_verdict["compared"],
            "faults": res["faults"],
            "state_gap_by_layer": readings.get("state_gap_by_layer"),
            "control_state_gap_by_layer": control.get("state_gap_by_layer"),
            "by_margin": readings.get("by_margin"),
            "control_by_margin": readings.get("control_by_margin"),
            "replay_tokens_same": readings.get("replay_tokens_same"),
            "end_to_end": res["end_to_end"]}
    print(json.dumps(line), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "a") as f:
            f.write(json.dumps(line) + "\n")
    return 0 if line["program_correct"] and not line["control_correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
