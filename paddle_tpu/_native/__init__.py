"""Native runtime loader: compiles native.cpp with the system toolchain on
first import, mirroring the reference's compiled core
(`paddle.base.core`). The built library sits next to the source under a
name keyed by a hash of native.cpp and the interpreter's ABI tag, so a
binary built from other source or for another Python is never loaded;
it is written atomically because fleet replicas import at the same
time. ``lib`` is None if no compiler is available or the build fails —
callers must degrade gracefully.
"""
from __future__ import annotations

import glob
import hashlib
import importlib.util
import os
import subprocess
import sys
import sysconfig

_here = os.path.dirname(os.path.abspath(__file__))
_src = os.path.join(_here, "native.cpp")


def _so_path() -> str:
    with open(_src, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:12]
    abi = sysconfig.get_config_var("SOABI") or "abi-unknown"
    return os.path.join(_here, f"_paddle_native.{digest}.{abi}.so")


def _build(so: str) -> bool:
    include = sysconfig.get_paths()["include"]
    tmp = f"{so}.tmp.{os.getpid()}"
    cmd = [
        "g++", "-O2", "-std=c++17", "-shared", "-fPIC",
        f"-I{include}", _src, "-o", tmp, "-lpthread",
    ]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=300)
    except (OSError, subprocess.TimeoutExpired):
        return False
    if proc.returncode != 0:
        sys.stderr.write(
            f"paddle_tpu: native build failed:\n{proc.stderr[-2000:]}\n")
        return False
    os.replace(tmp, so)  # atomic: a concurrent importer sees all or nothing
    for stale in glob.glob(os.path.join(_here, "_paddle_native*.so")):
        if stale != so:
            try:
                os.remove(stale)
            except OSError:
                pass
    return True


def _load():
    so = _so_path()
    if not os.path.exists(so) and not _build(so):
        return None
    spec = importlib.util.spec_from_file_location("_paddle_native", so)
    try:
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod
    except ImportError:
        return None


lib = _load()

if lib is not None:
    # back-fill flags that paddle_tpu.core.flags defined before the native
    # registry existed (the python side mirrors lazily; see flags._native_lib)
    try:
        from ..core import flags as _flags
        for _name, _info in _flags._registry.items():
            lib.flag_define(_name, str(_info.value), _info.help)
    except Exception:
        pass
