"""Faults planted in the program's timed path, to show that the comparison
catches them (``tests/test_faults.py`` on the CPU, ``control.py --fault``
on the chip).

Each fault wraps the program's scanned period step — ``fed.engine.
_period_step`` for the MLP, ``fed.model_engine._model_period_step`` for
the big-model families — and clears the program caches that hold the
compiled trajectories, so the next grid call traces the broken step:

* ``stale_state`` — the step returns the weights it was given (the state
  is left unchanged; losses and accuracy still computed);
* ``half_batch`` — each client keeps only the first half of its B_k
  examples (the mean is taken over the rest);
* ``altered_answer`` — the step reports the loss before its update in
  place of the loss after it (the answer altered where it is produced).

No fault crosses chips: no cell exchanges anything between chips.
"""
from __future__ import annotations

import contextlib
from functools import wraps

FAULTS = ("stale_state", "half_batch", "altered_answer")


def _targets():
    from repro.fed import engine, model_engine
    return [(engine, "_period_step", engine._trajectory_fn),
            (model_engine, "_model_period_step",
             model_engine._model_trajectory_fn)]


def _broken(step, fault: str):
    import jax.numpy as jnp

    @wraps(step)
    def run(*args):
        *head, carry, xs = args
        if fault == "half_batch":
            w = xs["weight"]
            keep = jnp.cumsum(w, axis=-1) <= jnp.ceil(
                jnp.sum(w, axis=-1, keepdims=True) / 2)
            xs = dict(xs, weight=w * keep)
        new_carry, (loss, acc, decay) = step(*head, carry, xs)
        if fault == "stale_state":
            state, residual = new_carry
            old = carry[0]
            if hasattr(state, "params"):          # big-model TrainState
                state = type(state)(old.params, state.opt, state.step,
                                    state.residual)
            else:
                state = old
            new_carry = (state, residual)
        elif fault == "altered_answer":
            loss = loss + decay                  # decay = before − after
        return new_carry, (loss, acc, decay)

    return run


@contextlib.contextmanager
def planted(fault: str):
    """Run the program with ``fault`` planted in its period step."""
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")
    saved = []
    for mod, name, cache in _targets():
        saved.append((mod, name, getattr(mod, name), cache))
        setattr(mod, name, _broken(getattr(mod, name), fault))
        cache.cache_clear()
    try:
        yield
    finally:
        for mod, name, step, cache in saved:
            setattr(mod, name, step)
            cache.cache_clear()
