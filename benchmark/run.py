"""Run one cell of the benchmark once.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is data found by the names in
`BENCHMARK.json`: the configuration (`configs[].file`), its role runner
(`benchmark/runners/<runner>.py`, named in the configuration file), the traffic
mix (`benchmark/traffic/<traffic>.json`), the limits that decide `correct`
(`benchmark/limits/<cell>.json`) and each per-layer metric
(`benchmark/metrics/<metric>.json`, which names its reader function under
`benchmark/readers/`). A later PR adds entries and files; nothing here is
edited for them.

The last line of standard output is the result object. Without a TPU the
command fails (exit 3) unless `--rehearsal` is given; a rehearsal drives the
same control flow at a tiny size on whatever JAX finds and prints counts only.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()          # set-up is counted from here

import argparse
import importlib
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmark")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")   # git-ignored, emptied per run


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name: str) -> dict:
    """The manifest entries and files of one cell."""
    manifest = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise SystemExit(f"run.py: no workload {name!r} in BENCHMARK.json; "
                         f"it has {sorted(cells)}")
    cell = cells[name]
    entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    return {"manifest": manifest, "cell": cell, "config_entry": entry,
            "config": load_json(ROOT, entry["file"]),
            "traffic": load_json(HERE, "traffic", cell["traffic"] + ".json"),
            "limits": load_json(HERE, "limits", name + ".json")}


def metrics_of(manifest: dict, group: str, cell_name: str) -> list:
    """Entries of `end_to_end` or `per_layer` that this cell reports."""
    return [m for m in manifest[group]
            if "workloads" not in m or cell_name in m["workloads"]]


def device_info(jax) -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_bytes(dev) -> int:
    """Device bytes held now, the running program's temporaries included:
    PJRT's `peak_bytes_in_use` leaves those out on the v5e (PERF.md, PR 21),
    so the peak is the highest of this sum that the run's samples see."""
    st = dev.memory_stats() or {}
    return max(int(st.get("peak_bytes_in_use", 0)),
               int(st.get("bytes_in_use", 0)) + int(st.get("bytes_reserved", 0)))


class Context:
    """What a runner gets: the cell's data, the clock's origin, the tracer."""

    def __init__(self, args, loaded, jax):
        self.jax = jax
        self.seed = int(args.seed)
        self.seconds = float(args.seconds)
        self.trace = bool(args.trace)
        self.rehearsal = bool(args.rehearsal)
        self.t_start = T_START
        self.cell = loaded["cell"]
        self.config = loaded["config"]
        self.traffic = loaded["traffic"]
        self.devices = jax.devices()[: self.cell["chips"]]
        # a traced run measures the traffic file's trace_seconds at most: the
        # trace of a whole window is large and its reading slow
        self.window_seconds = (
            min(self.seconds, float(self.traffic.get("trace_seconds", self.seconds)))
            if self.trace else self.seconds)
        self._mem_peak = 0
        # set by tests/control_on_chip.py alone: the precision one step below
        # the configuration's, in which the runner then also reads the control
        self.control = None

    def log(self, msg: str) -> None:
        print(f"[bench {time.perf_counter() - T_START:7.1f}s] {msg}",
              file=sys.stderr, flush=True)

    def sample_memory(self) -> int:
        now = max(memory_bytes(d) for d in self.devices)
        self._mem_peak = max(self._mem_peak, now)
        return now

    @property
    def memory_peak_bytes(self) -> int:
        return self._mem_peak

    def trace_start(self) -> None:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = self.jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0        # host spans come from TraceAnnotation
        opts.host_tracer_level = 2
        self.jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
        self._span = self.annotate("bench.window")
        self._span.__enter__()

    def trace_stop(self) -> None:
        self._span.__exit__(None, None, None)
        self.jax.profiler.stop_trace()

    def annotate(self, name: str):
        return self.jax.profiler.TraceAnnotation(name)


def read_per_layer(loaded, ctx, observed, reduced) -> dict:
    """Call each per-layer metric's reader; a reader that finds nothing to
    read returns None and the metric is left out of the line."""
    from benchmark.lib import peaks
    obs = {"trace": reduced, "run": observed, "config": ctx.config,
           "traffic": ctx.traffic, "cell": ctx.cell,
           # a rehearsal's values are thrown away; any peak drives the code
           "peaks": peaks.PEAKS["TPU v5 lite"] if ctx.rehearsal
           else peaks.peaks_for(ctx.devices[0].device_kind)}
    out = {}
    for m in metrics_of(loaded["manifest"], "per_layer", ctx.cell["name"]):
        spec = load_json(HERE, "metrics", m["name"] + ".json")
        mod, fn = spec["reader"].rsplit(".", 1)
        value = getattr(importlib.import_module(f"benchmark.readers.{mod}"), fn)(
            obs, **spec.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", action="store_true",
                    help="leave the raw trace in .bench_trace/ for a look by hand")
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny sizes on any platform; counts only, no device metric")
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    loaded = load_cell(args.workload)
    if args.seconds is None:
        args.seconds = loaded["manifest"]["run_seconds"]
    if not os.path.isdir(os.path.join(ROOT, "paddle_tpu")):
        print("run.py: the system under test (paddle_tpu/) is not in this "
              "checkout", file=sys.stderr)
        return 4

    import jax
    device = device_info(jax)
    chips = loaded["cell"]["chips"]
    if not args.rehearsal and (device["platform"] != "tpu"
                               or device["count"] < chips):
        print(f"run.py: the cell needs {chips} TPU chip(s); JAX found "
              f"{device['count']} x {device['platform']!r}. A CPU dry run of "
              f"the control flow is --rehearsal.", file=sys.stderr)
        return 3

    ctx = Context(args, loaded, jax)
    runner = importlib.import_module(
        f"benchmark.runners.{loaded['config']['runner']}")
    res = runner.run(ctx)

    from benchmark.lib import check
    verdict = check.judge(res["compared"], loaded["limits"], ctx.rehearsal)
    correct = verdict["correct"] and not res.get("faults")

    device = dict(device, memory_peak_bytes=ctx.memory_peak_bytes)
    line = {"correct": bool(correct), "attempted": int(res["attempted"]),
            "failed": int(res["failed"])}
    reduced = None
    if ctx.trace:
        from benchmark.lib import trace_reduce
        reduced = trace_reduce.reduce_dir(TRACE_DIR, n_devices=chips)
        per_layer = read_per_layer(loaded, ctx, res["observed"], reduced)
        if not args.keep_trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
    if args.rehearsal:
        # counts only: a rehearsal's clock and trace say nothing of the chip
        line["rehearsal"] = True
        line["metrics"] = {}
        line["counts"] = dict(res.get("counts", {}))
        if reduced is not None:
            line["counts"]["per_layer_read"] = sorted(per_layer)
            line["counts"]["traced_ops"] = reduced["n_ops"]
    elif ctx.trace:
        line["metrics"] = per_layer
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        line["breakdown"] = {"device_ops": reduced["top_ops"][:10],
                             "idle_gaps": reduced["idle_gaps"][:10]}
    else:
        units = {m["name"]: m["unit"] for m in
                 metrics_of(loaded["manifest"], "end_to_end", ctx.cell["name"])}
        line["metrics"] = {k: {"value": float(v), "unit": units[k]}
                           for k, v in res["end_to_end"].items()}
    line["device"] = device
    line["compared"] = verdict["compared"]      # last, as the contract asks
    for fault in res.get("faults", []):
        print(f"run.py: fault: {fault}", file=sys.stderr)
    for name, c in verdict["compared"].items():
        print(f"compared {name}: {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['ok'] else 'OVER'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
