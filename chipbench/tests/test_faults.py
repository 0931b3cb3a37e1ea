"""A run with the timed path broken underneath must come out not correct.

Each fault is planted in the program's ``ClassifierServer.lanes_step``,
the call that advances every lane by one fused step and hands the
scheduler its answers.  The exchange between chips is not among them: the
lane-sharded step has no collective, replicas never talk to each other."""
import json

import numpy as np
import pytest

from repro.serving.engine import ClassifierServer

from chipbench import run

ONE_CHIP = [w["name"] for w in json.loads((run.ROOT / "BENCHMARK.json").read_text())["workloads"]
            if w["chips"] == 1]


def unchanged_state(orig):
    def lanes_step(self, bucket, active):
        h = self._bstate[bucket]["h"]
        out = orig(self, bucket, active)
        self._bstate[bucket]["h"] = h        # the step's new state is dropped
        return out
    return lanes_step


def half_the_lanes(orig):
    def lanes_step(self, bucket, active):
        step = self._step

        def half_step(params, h, act, lengths, thr):
            # the fused step computes the first half of the lanes only
            return step(params, h, act.at[act.shape[0] // 2:].set(False), lengths, thr)

        self._step = half_step
        try:
            return orig(self, bucket, active)
        finally:
            self._step = step
    return lanes_step


def _rewrite(orig, change):
    def lanes_step(self, bucket, active):
        lg, ent, retire, decision = orig(self, bucket, active)
        lg = change(np.array(lg), self)
        self._bstate[bucket]["out"] = (lg, ent, retire, decision)
        return self._bstate[bucket]["out"]
    return lanes_step


def altered_answer(orig):
    # lane 0's logits moved by ten times the limit, where they are produced
    def change(lg, srv):
        lg[0] += 10 * srv.limit
        return lg
    return _rewrite(orig, change)


def swapped_answers(orig):
    # the scheduler's bookkeeping: lanes 0 and 1 hand back each other's answer
    return _rewrite(orig, lambda lg, srv: lg[[1, 0] + list(range(2, len(lg)))])


@pytest.mark.parametrize("fault", [unchanged_state, half_the_lanes, altered_answer,
                                   swapped_answers])
@pytest.mark.parametrize("cell", ONE_CHIP)
def test_fault_is_not_correct(smoke_run, monkeypatch, fault, cell):
    limit = run.load_cell(cell)[2]["checks"]["logit_err"]
    monkeypatch.setattr(ClassifierServer, "limit", limit, raising=False)
    monkeypatch.setattr(ClassifierServer, "lanes_step", fault(ClassifierServer.lanes_step))
    rc, out = smoke_run(cell, seconds=1.0)
    assert rc == 0
    assert out["correct"] is False, out["checks"]


@pytest.mark.parametrize("cell", ONE_CHIP)
def test_sound_run_is_correct(smoke_run, cell):
    rc, out = smoke_run(cell, seconds=1.0)
    assert rc == 0 and out["correct"] is True
