"""The host ledger's checks catch a plan that breaks the paper's rules."""
import numpy as np
import pytest

import reference

B_MAX, K, P, SLOT = 4, 2, 3, 4


def _plan(policy):
    batch = (np.full((1, P, K), B_MAX, np.float64) if policy == "full"
             else np.array([[[1, 3], [2, 2], [4, 1]]], np.float64))
    gb = batch.sum(2)
    weight = (np.arange(SLOT)[None, None, None, :]
              < batch[..., None]).astype(np.float32)
    return {"active": np.ones((1, P, K), np.float32), "batch": batch,
            "idx": np.zeros((1, P, K, SLOT), np.int64), "weight": weight,
            "lr": 0.1 * np.sqrt(gb / 8.0), "aggden": np.zeros((1, P)),
            "times": np.array([[1.0, 2.0, 3.0]]),
            "global_batch": gb.astype(np.int64)}


def _faults(arrays, policy):
    parts = [[np.arange(10), np.arange(10)]]
    return reference.ledger_faults(
        arrays, parts, arrays["times"], arrays["global_batch"], B_MAX, K,
        [policy], 0.1, 8.0)


@pytest.mark.parametrize("policy", ["full", "proposed"])
def test_sound_plan_has_no_faults(policy):
    assert _faults(_plan(policy), policy) == []


def test_learning_rate_off_the_scaling_law():
    arrays = _plan("proposed")
    arrays["lr"] = arrays["lr"] * 1.01
    assert any("learning rate" in f for f in _faults(arrays, "proposed"))


def test_fixed_policy_with_another_batch():
    arrays = _plan("proposed")
    assert any("full policy" in f for f in _faults(arrays, "full"))
