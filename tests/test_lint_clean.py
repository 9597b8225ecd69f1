"""The source linter AND the static capture pass run clean inside
tier-1.

Same pattern as test_flags_docs.py: the rule set + allowlist are pinned
together, so a new violation (an unguarded registry sweep, a stray
.numpy() on a hot path, a bare except, a fusable marker without its
impl, an unallowlisted graph break in a step function) fails tests
instead of landing silently. Deliberate exceptions go in
paddle_tpu/analysis/allowlist.py WITH a justification — never by
weakening a rule.
"""
import paddle_tpu  # noqa: F401 — ops.yaml + fusion registries loaded
from paddle_tpu.analysis.lint import lint


def test_repo_lints_clean():
    r = lint()
    assert not r.parse_errors, r.parse_errors
    assert not r.diagnostics, (
        "lint violations introduced:\n"
        + "\n".join(d.render() for d in r.diagnostics)
        + "\n\nfix the site, or add a justified entry to "
          "paddle_tpu/analysis/allowlist.py")


def test_lint_scans_the_whole_package():
    r = lint()
    assert r.files_scanned > 150  # the package, not a subset


def test_suppressions_are_justified():
    from paddle_tpu.analysis.allowlist import (ALLOWLIST,
                                               CAPTURE_ALLOWLIST)
    for rule, pattern, why in ALLOWLIST + CAPTURE_ALLOWLIST:
        assert rule and pattern, (rule, pattern)
        assert len(why.split()) >= 4, (
            f"allowlist entry ({rule}, {pattern!r}) needs a real "
            f"justification, got {why!r}")


def test_repo_step_functions_capture_clean():
    """The static capture pass over the package's own step functions
    (hapi train/eval batch, serving decode step): a new
    unallowlisted PTC diagnostic — a fresh graph break landing in a
    step path — fails CI here, exactly like a lint violation."""
    from paddle_tpu.analysis.capture import scan_repo_steps
    r = scan_repo_steps()
    assert not r.diagnostics, (
        "capture-plan violations introduced in step functions:\n"
        + "\n".join(d.render() for d in r.diagnostics)
        + "\n\nfix the break (hoist the read, move the side effect to "
          "the step boundary), or add a justified CAPTURE_ALLOWLIST "
          "entry in paddle_tpu/analysis/allowlist.py")
    assert len(r.functions) >= 5  # the step inventory actually scanned
