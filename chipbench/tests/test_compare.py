"""The comparison that decides ``correct``, on hand-made answers."""
import numpy as np

from chipbench.drivers import classifier

LIMITS = {"logit_err": 1e-5, "logit_err_mean": 3e-6, "exit_band": 4e-5}


def _reference():
    logits = np.tile(np.arange(3.0), (2, 4, 1))              # [N, L, C]
    ents = np.array([[0.9, 0.5, 0.9, 0.9], [0.9, 0.9, 0.9, 0.9]])
    return logits, ents


def test_sound_answers_pass():
    logits, ents = _reference()
    got = classifier.compare([(logits[0, 1] + 1e-6, 2), (logits[1, 3], 4)], logits, ents,
                             0.6, LIMITS)
    assert abs(got["logit_err"][0] - 1e-6) < 1e-12 and got["exit_mismatch"] == (0, 0)
    assert abs(got["logit_err_mean"][0] - 5e-7) < 1e-12


def test_every_answer_a_little_off_fails_the_mean():
    # each gap under the widest limit, their mean over its own
    logits, ents = _reference()
    got = classifier.compare([(logits[0, 1] + 5e-6, 2), (logits[1, 3] - 5e-6, 4)], logits,
                             ents, 0.6, LIMITS)
    assert got["logit_err"][0] <= LIMITS["logit_err"]
    assert got["logit_err_mean"][0] > LIMITS["logit_err_mean"]


def test_wrong_exit_and_nan_fail():
    logits, ents = _reference()
    got = classifier.compare([(logits[0, 3], 4), (np.full(3, np.nan), 4)], logits, ents,
                             0.6, LIMITS)
    assert got["exit_mismatch"][0] == 1
    assert got["logit_err"][0] == np.inf
    nan_ref = ents.copy()
    nan_ref[1, 2] = np.nan
    got = classifier.compare([(logits[0, 1], 2), (logits[1, 3], 4)], logits, nan_ref,
                             0.6, LIMITS)
    assert got["exit_mismatch"][0] == 1


def test_exit_forgiven_only_inside_the_band():
    logits, ents = _reference()
    near = ents.copy()
    near[1, 0] = 0.6 + 2e-5                # the reference stays, the server exited
    got = classifier.compare([(logits[1, 0], 1)], logits[1:], near[1:], 0.6, LIMITS)
    assert got["exit_mismatch"] == (0, 0)
    near[1, 0] = 0.6 + 1e-4
    got = classifier.compare([(logits[1, 0], 1)], logits[1:], near[1:], 0.6, LIMITS)
    assert got["exit_mismatch"] == (1, 0)
