"""The SBC threshold search against the plain 20-step binary bisection.

``topk_threshold_bisect`` resolves several bisection steps per pass over
the magnitudes; its result must be the binary loop's bit for bit.  Both
sides are compiled with ``jax.jit``: XLA may contract ``hi``'s
``max * (1 + 1e-6) + 1e-30`` into a fused multiply-add in one compile
context and not in another, which moves ``hi`` by one ulp.
"""
from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import Experiment, ScenarioSpec, SerialExecutor
from repro.compression import sbc
from repro.core import DeviceProfile
from repro.data.pipeline import ClassificationData
from repro.fed import engine

SIZES = (10, 256, 65_536, 786_432)


def binary_bisect(mag, k, iters=20):
    """The reference: one count pass over ``mag`` per bisection step."""
    lo = jnp.zeros((), jnp.float32)
    hi = jnp.max(mag) * (1.0 + 1e-6) + 1e-30

    def body(_, lohi):
        lo, hi = lohi
        mid = 0.5 * (lo + hi)
        geq = jnp.sum(mag >= mid) >= k
        return jnp.where(geq, mid, lo), jnp.where(geq, hi, mid)

    lo, hi = jax.lax.fori_loop(0, iters, body, (lo, hi))
    return lo


def _clients(n, seed=0):
    """Six clients' leaves of ``n`` values, one case each."""
    z = np.random.default_rng(seed).standard_normal((4, n))
    return np.stack([
        z[0],                        # random normals
        np.round(z[1] * 2) / 2,      # heavy ties
        np.zeros(n),                 # an all-zero leaf (hi = 1e-30)
        np.abs(z[2]),                # one sign: positive
        -np.abs(z[3]),               # one sign: negative
        np.full(n, 0.75),            # every value tied
    ]).astype(np.float32)


def _assert_bitwise(got, want):
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want), strict=True):
        np.testing.assert_array_equal(np.asarray(a).view(np.uint32),
                                      np.asarray(b).view(np.uint32))


def _k(n, which):
    return {"1": 1, "r": max(1, round(n * 0.005)), "n": n}[which]


@pytest.mark.parametrize("n,which,iters",
                         [(n, w, 20) for n in SIZES for w in "1rn"]
                         + [(256, "r", 7), (65_536, "1", 4)],
                         ids=lambda v: str(v))
def test_threshold_is_the_binary_loops_bitwise(n, which, iters):
    mag = jnp.abs(_clients(n, seed=n))
    k = _k(n, which)
    want = jax.jit(jax.vmap(lambda m: binary_bisect(m, k, iters)))(mag)
    got = jax.jit(jax.vmap(
        lambda m: sbc.topk_threshold_bisect(m, k, iters)))(mag)
    _assert_bitwise(got, want)


@pytest.mark.parametrize("ratio", [0.005, 1.0])
def test_compress_dense_is_the_binary_loops_bitwise(monkeypatch, ratio):
    """Outputs and residuals of ``compress_dense`` over leaves of every
    size, vmapped over the six clients, with error feedback."""
    grads = {str(n): jnp.asarray(_clients(n, seed=n)) for n in SIZES}
    residual = {str(n): jnp.asarray(_clients(n, seed=n + 1)) * 0.1
                for n in SIZES}

    def run():
        return jax.jit(jax.vmap(
            lambda g, r: sbc.compress_dense(g, ratio, r)))(grads, residual)

    got = run()
    monkeypatch.setattr(sbc, "topk_threshold_bisect", binary_bisect)
    want = run()
    _assert_bitwise(got, want)


def test_engine_series_are_the_binary_loops_bitwise(monkeypatch):
    """A compressed FEEL run (2 rows, K = 6, 3 periods) gives the same loss
    and accuracy series with the binary loop in the period step."""
    full = ClassificationData.synthetic(n=420, dim=19, seed=5, spread=6.0)
    data, test = full.split(60)
    fleet = tuple(DeviceProfile(kind="cpu", f_cpu=f * 1e9)
                  for f in (0.5, 0.8, 1.1, 1.4, 1.7, 2.0))
    spec = ScenarioSpec(fleet=fleet, name="bisect6", hidden=23, b_max=8,
                        compress=True, seeds=(0, 1))

    def run():
        return Experiment(data, test, [spec]).run(
            3, executor=SerialExecutor())

    got = run()
    # a program cache of its own, so the reference traces afresh and the
    # process's cache and trace ledger stay as they were
    monkeypatch.setattr(sbc, "topk_threshold_bisect", binary_bisect)
    monkeypatch.setattr(engine, "_trajectory_fn", lru_cache(maxsize=None)(
        engine._trajectory_fn.__wrapped__))
    with engine.suspend_trace_count():
        want = run()
    assert np.asarray(got.losses).shape == (2, 3)
    _assert_bitwise((got.losses, got.accs), (want.losses, want.accs))
