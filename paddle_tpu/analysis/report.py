"""One reporting surface over the three analyzers.

``report()`` composes a capture audit (when given a callable), a full
source-lint pass and, when a lock auditor is active, its summary into
one :class:`AnalysisReport` with a single ``diagnostics`` list and a
text/dict rendering. ``self_check()`` is the smoke contract
(``python -m paddle_tpu.analysis --self-check``): one seeded bug per
analyzer, each of which must be detected by its rule id — proving the
analysis plane itself works before anyone trusts a clean report.
"""
from __future__ import annotations

import threading
from typing import Any, Callable, Dict, List, Optional

from .diagnostics import Diagnostic, RULES, sort_diagnostics

__all__ = ["AnalysisReport", "report", "self_check", "rules_table"]


class AnalysisReport:
    def __init__(self, capture=None, lint_result=None, locks_summary=None):
        self.capture = capture
        self.lint = lint_result
        self.locks_summary = locks_summary

    @property
    def diagnostics(self) -> List[Diagnostic]:
        out: List[Diagnostic] = []
        if self.capture is not None:
            out.extend(self.capture.diagnostics)
        if self.lint is not None:
            out.extend(self.lint.diagnostics)
        return sort_diagnostics(out)

    @property
    def errors(self) -> List[Diagnostic]:
        return [d for d in self.diagnostics if d.severity == "error"]

    def to_dict(self) -> Dict[str, Any]:
        d: Dict[str, Any] = {
            "diagnostics": [x.to_dict() for x in self.diagnostics]}
        if self.capture is not None:
            d["capture"] = self.capture.to_dict()
        if self.lint is not None:
            d["lint"] = {
                "files_scanned": self.lint.files_scanned,
                "findings": len(self.lint.diagnostics),
                "allowlisted": len(self.lint.suppressed),
            }
        if self.locks_summary is not None:
            d["locks"] = self.locks_summary
        return d

    def render(self) -> str:
        parts = ["paddle_tpu.analysis report",
                 "=" * 26]
        if self.capture is not None:
            parts.append(self.capture.render())
        if self.lint is not None:
            parts.append(self.lint.render())
        if self.locks_summary is not None:
            cyc = self.locks_summary.get("cycles", [])
            parts.append(f"locks: {len(self.locks_summary.get('locks', {}))}"
                         f" instrumented, {len(cyc)} cycle(s)"
                         + (": " + "; ".join(cyc) if cyc else ""))
        errs = self.errors
        parts.append(f"total: {len(self.diagnostics)} diagnostic(s), "
                     f"{len(errs)} error(s)")
        return "\n".join(parts)


def report(fn: Optional[Callable] = None, *args, lint: bool = True,
           warmup: int = 2, **kwargs) -> AnalysisReport:
    """The one-stop entry point. With ``fn``, runs a capture audit of
    ``fn(*args, **kwargs)`` (see :func:`analysis.audit` — e.g. one
    ``Model.fit`` step closure); with ``lint=True`` (default) also runs
    the source linter over ``paddle_tpu/``. When a lock auditor is
    active (``locks.instrument()``), its summary is attached."""
    capture = None
    if fn is not None:
        from .auditor import audit
        capture = audit(fn, *args, warmup=warmup, **kwargs)
    lint_result = None
    if lint:
        from .lint import lint as _lint
        lint_result = _lint()
    from . import locks as _locks
    la = _locks.active_auditor()
    locks_summary = la.summary() if la is not None else None
    return AnalysisReport(capture, lint_result, locks_summary)


def rules_table() -> str:
    lines = ["rule    analyzer  severity  title",
             "-" * 64]
    for rid, info in sorted(RULES.items()):
        lines.append(f"{rid:<7} {info.analyzer:<9} {info.severity:<9} "
                     f"{info.title}")
    return "\n".join(lines)


def self_check(verbose: bool = False) -> Dict[str, Any]:
    """Seed one bug per analyzer and assert its rule fires — the smoke
    proof that the analysis plane detects what it claims to: lint,
    audit, capture (one break per PTC rule), shapes (a wrong spec
    fails the golden run), flight (a synthetic crash leaves a dump
    containing the seeded event) and locks. Returns {"ok": bool,
    "checks": {name: bool}, "detail": str}. About a second on the
    CPU."""
    checks: Dict[str, bool] = {}
    details: List[str] = []

    # 1) lint engine: bare except + unguarded registry sweep
    try:
        from .lint import lint_source
        diags = lint_source(
            "REG = {}\n"
            "def evict():\n"
            "    REG.clear()\n"
            "    try:\n"
            "        pass\n"
            "    except:\n"
            "        pass\n")
        rules = {d.rule for d in diags}
        checks["lint"] = {"PTL003", "PTL004"} <= rules
        if not checks["lint"]:
            details.append(f"lint fired {sorted(rules)}, "
                           f"wanted PTL003+PTL004")
    except Exception as e:  # noqa: BLE001 — a crash IS the failure
        checks["lint"] = False
        details.append(f"lint self-check crashed: {e!r}")

    # 2) auditor: a fused chain broken by a host sync must be captured
    #    with its flush reason and a PTA001 sync diagnostic
    try:
        import numpy as np
        from .auditor import audit

        def step():
            import paddle_tpu as paddle
            x = paddle.to_tensor(np.ones((4, 4), np.float32))
            y = paddle.add(paddle.multiply(x, 2.0), 1.0)
            return float(y.sum().item())  # lint-allow: PTL001 seeded bug

        rep = audit(step, warmup=1)
        checks["audit"] = (
            any(d.rule == "PTA001" for d in rep.diagnostics)
            and len(rep.flushes) > 0
            and all(f["origin"] != "<unknown>" for f in rep.flushes))
        if not checks["audit"]:
            details.append(
                f"audit: {len(rep.flushes)} flushes, rules "
                f"{sorted({d.rule for d in rep.diagnostics})}")
    except Exception as e:  # noqa: BLE001
        checks["audit"] = False
        details.append(f"audit self-check crashed: {e!r}")

    # 3) capture planner, static half: one seeded break per PTC rule —
    #    a tensor-valued branch, an in-place store, a tail host read and
    #    a boolean-mask gather — each detected by exact id
    try:
        from .capture import scan_source
        diags = scan_source(
            "def step(x):\n"
            "    import paddle_tpu as paddle\n"
            "    t = paddle.multiply(x, 2.0)\n"
            "    if t.sum().item() > 0:\n"          # PTC001
            "        t = paddle.add(t, 1.0)\n"
            "    t[0] = 0.0\n"                      # PTC002
            "    mask = t > 0.5\n"
            "    sel = t[mask]\n"                   # PTC004
            "    return sel.numpy()\n")             # PTC003
        rules = {d.rule for d in diags}
        want = {"PTC001", "PTC002", "PTC003", "PTC004"}
        checks["capture"] = want <= rules
        if not checks["capture"]:
            details.append(f"capture fired {sorted(rules)}, "
                           f"wanted {sorted(want)}")
    except Exception as e:  # noqa: BLE001
        checks["capture"] = False
        details.append(f"capture self-check crashed: {e!r}")

    # 4) shape specs: a deliberately wrong spec (sum graded as
    #    elementwise) must fail the golden run as PTC005, and the real
    #    table must pass it
    try:
        from .shapes import validate_op
        seeded = validate_op("sum", "elementwise")
        clean = validate_op("sum")
        checks["shapes"] = (
            any(d.rule == "PTC005" for d in seeded) and not clean)
        if not checks["shapes"]:
            details.append(
                f"shapes: seeded={[d.rule for d in seeded]}, "
                f"clean={[d.rule for d in clean]}")
    except Exception as e:  # noqa: BLE001
        checks["shapes"] = False
        details.append(f"shapes self-check crashed: {e!r}")

    # 5) flight recorder: a synthetic crash (unhandled exception on a
    #    thread, the serving-loop death mode) must leave a dump whose
    #    trail contains the event seeded just before the crash. The
    #    check runs against freshly installed hooks (a production
    #    install is torn down first and re-installed after — a second
    #    install_crash_hooks() is an idempotent no-op, so silencing the
    #    thread hook without this would disarm the live hooks and fail
    #    spuriously), forces the recorder ON (an operator kill switch
    #    must not read as a broken analysis plane), and afterwards
    #    removes its synthetic events from the production ring so a
    #    later REAL dump doesn't carry a fake prior crash. The one
    #    honest residue: dumps_total{trigger=exception} counts the
    #    synthetic dump it really wrote.
    try:
        import tempfile

        from ..core.flags import get_flags, set_flags
        from ..observability import flight

        _SEEDED_MSG = "flight self-check seeded crash"
        with tempfile.TemporaryDirectory() as d:
            prev_flags = get_flags(["FLAGS_flight_dump_dir",
                                    "FLAGS_flight_recorder"])
            was_installed = flight._hooks_installed
            # signal numbers bound by a production
            # install_crash_hooks(signals=...) must be re-bound on
            # re-install or the operator's live-dump trigger silently
            # reverts to SIG_DFL
            prev_signums = tuple(flight._prev_signals)
            if was_installed:
                flight.uninstall_crash_hooks()
            prev_hook = threading.excepthook
            # silence the default traceback print: the crash is seeded
            threading.excepthook = lambda args: None
            set_flags({"FLAGS_flight_dump_dir": d,
                       "FLAGS_flight_recorder": 1})
            flight.install_crash_hooks()
            try:
                flight.record("selfcheck", "seeded_event", probe=1)

                def boom():
                    raise RuntimeError(_SEEDED_MSG)

                t = threading.Thread(target=boom)
                t.start()
                t.join()
                dumps = flight.find_dumps(d)
                ok_flight = False
                if dumps:
                    _hdr, evs = flight.load_dump(dumps[0])
                    ok_flight = any(
                        e.get("cat") == "selfcheck"
                        and e.get("name") == "seeded_event"
                        for e in evs)
            finally:
                flight.uninstall_crash_hooks()
                threading.excepthook = prev_hook
                set_flags(prev_flags)
                if was_installed:
                    flight.install_crash_hooks(signals=prev_signums)
                flight._discard_events(
                    lambda ev: ev[1] == "selfcheck" or (
                        ev[1] == "crash"
                        and _SEEDED_MSG in str(ev[5] or "")))
        checks["flight"] = ok_flight
        if not ok_flight:
            details.append(
                f"flight: {len(dumps)} dump(s), seeded event missing")
    except Exception as e:  # noqa: BLE001
        checks["flight"] = False
        details.append(f"flight self-check crashed: {e!r}")

    # 6) lock shim: an AB/BA inversion must come back as a PTK001 cycle
    try:
        from .locks import LockAuditor
        aud = LockAuditor()
        a, b = aud.lock("selfcheck.A"), aud.lock("selfcheck.B")

        def ab():
            with a:
                with b:
                    pass

        def ba():
            with b:
                with a:
                    pass

        ab()
        t = threading.Thread(target=ba)
        t.start()
        t.join()
        diags = aud.diagnostics()
        checks["locks"] = any(d.rule == "PTK001" for d in diags)
        if not checks["locks"]:
            details.append(f"locks: edges {list(aud.edges)}, no cycle")
    except Exception as e:  # noqa: BLE001
        checks["locks"] = False
        details.append(f"locks self-check crashed: {e!r}")

    ok = all(checks.values())
    out = {"ok": ok, "checks": checks, "detail": "; ".join(details)}
    if verbose:
        status = "OK" if ok else "FAIL"
        print(f"analysis self-check: {status} "
              + " ".join(f"{k}={'ok' if v else 'FAIL'}"
                         for k, v in checks.items())
              + (f" ({out['detail']})" if details else ""))
    return out
