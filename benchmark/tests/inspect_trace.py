"""A look by hand at a trace that `run.py --keep-trace` left in .bench_trace/:
the planes and lines, and the time by operation for the names that hold a
pattern.   python3 benchmark/tests/inspect_trace.py [pattern ...]"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402

from benchmark.lib import trace_reduce  # noqa: E402

pd = jax.profiler.ProfileData.from_file(
    trace_reduce.find_xplane(os.path.join(ROOT, ".bench_trace")))
print(trace_reduce.describe(pd, limit=3)[:6000])
red = trace_reduce.reduce_profile(pd)
print(f"window {red['window_s']:.4f} s busy {red['busy_s']:.4f} s, {red['n_ops']} ops")
for pattern in sys.argv[1:] or ["custom-call"]:
    print(f"--- operations holding {pattern!r}")
    for name, rec in sorted(red["ops"].items(), key=lambda kv: -kv[1]["seconds"]):
        if pattern in name or pattern in rec["text"]:
            print(f"{rec['seconds']:.5f} s x{rec['count']:5d}  {name}   | {rec['text'][:400]}")
print("--- top 25")
for name, rec in sorted(red["ops"].items(), key=lambda kv: -kv[1]["seconds"])[:25]:
    print(f"{rec['seconds']:.5f} s x{rec['count']:5d}  {name}")
print("idle gaps", red["idle_gaps"])
