"""The controls of `serve.brumby14b.longdoc_closed32`, at the cell's own size, on
the chip:

    python3 benchmark/tests/control_retention_on_chip.py --seed 11 \
        --seconds 51 --control state_bf16 --out ctl_retention.jsonl

`tests/control_kda_on_chip.py` for this cell: the cell driven as run.py drives
it with `ctx.control` set, the line holding what the control reads beside what
the program reads:

- `fp8`: every matmul operand of the reference rounded to e4m3 (one step below
  the bfloat16 of the weights and activations): its `served_logit_gap`;
- `state_bf16`: the runner's `recurrence_probe` run a second time in bfloat16
  pools of the engine's shapes (one step below the float32 the configuration
  states for the state): its `recurrence_gap`, the same measure as the
  program's, on the same rows.

Exit 0 where the program is correct and the control is not.
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.tests import control_kda_on_chip as controls  # noqa: E402

controls.CELL = "serve.brumby14b.longdoc_closed32"

if __name__ == "__main__":
    sys.exit(controls.main())
