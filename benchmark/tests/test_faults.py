"""`correct` has to come out false when the timed path is broken underneath,
and when the reference computed one step of precision down stands in the
program's place (the control). Both at a size a test run can hold: the
rehearsal's, which skips the harness's look for a chip and nothing else."""
import json

import numpy as np
import pytest

from benchmark import run as harness

TRAIN, SERVE = "train.mistral7b.s2048", "serve.yi9b.chat_closed32"


def _line(capsys, cell, seed=11):
    rc = harness.main(["--workload", cell, "--seed", str(seed), "--seconds", "1",
                       "--trace", "0", "--rehearsal"])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_sound_runs_are_correct(capsys):
    assert _line(capsys, TRAIN)["correct"] is True
    assert _line(capsys, SERVE)["correct"] is True


def test_a_step_that_returns_its_state_unchanged(capsys, monkeypatch):
    from paddle_tpu.optimizer.optimizer import Adam
    monkeypatch.setattr(Adam, "_update", lambda self, p, g, state, lr: (p, dict(state)))
    line = _line(capsys, TRAIN)
    assert line["correct"] is False
    assert line["compared"]["param_change_gap"]["value"] == pytest.approx(1.0)


def test_half_of_the_batch_left_out(capsys, monkeypatch):
    from paddle_tpu.distributed.dist_train import DistTrainStep
    whole = DistTrainStep.__call__
    monkeypatch.setattr(
        DistTrainStep, "__call__",
        lambda self, *arrays, num_labels=1: whole(
            self, *[a[: len(a) // 2] for a in arrays], num_labels=num_labels))
    line = _line(capsys, TRAIN)
    assert line["correct"] is False
    assert not line["compared"]["grad_norm_gap"]["ok"]


def test_a_token_altered_where_it_is_produced(capsys, monkeypatch):
    from paddle_tpu.serving import PagedLlamaDecodeEngine
    step = PagedLlamaDecodeEngine.step
    calls = {"n": 0}

    def altered(self):
        out = np.array(step(self))
        calls["n"] += 1
        if calls["n"] % 3 == 0:
            out = (out + 1) % self.cfg.vocab_size
        return out
    monkeypatch.setattr(PagedLlamaDecodeEngine, "step", altered)
    line = _line(capsys, SERVE)
    assert line["correct"] is False
    assert not line["compared"]["served_logit_gap"]["ok"]


@pytest.mark.parametrize("cell", [TRAIN, SERVE])
def test_the_control_fails_a_number_of_the_cell(cell):
    """The reference in fp8, in the program's place, read by the runner's own
    code: at least one of its numbers is over the cell's limit."""
    import argparse
    import importlib

    import jax
    from benchmark.lib import check
    loaded = harness.load_cell(cell)
    ns = argparse.Namespace(seed=13, seconds=1.0, trace=0, rehearsal=True)
    ctx = harness.Context(ns, loaded, jax)
    ctx.control = "fp8"
    runner = importlib.import_module(f"benchmark.runners.{loaded['config']['runner']}")
    res = runner.run(ctx)
    assert check.judge(res["compared"], loaded["limits"], True)["correct"] is True
    control = res["observed"]["readings"]["control"]
    assert check.judge(control, loaded["limits"], True)["correct"] is False
    if "fault_half_batch" in res["observed"]["readings"]:
        fault = res["observed"]["readings"]["fault_half_batch"]
        assert check.judge(fault, loaded["limits"], True)["correct"] is False
