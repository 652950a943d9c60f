"""Big-model FEEL engine: transformer / Mamba-2 per-device train steps.

This is the ``model_family`` counterpart of :mod:`repro.fed.engine`'s
``feel_mlp`` scan.  A spec with ``model_family="transformer"`` or
``"mamba2"`` lowers to one jitted ``vmap(lax.scan)`` per bucket whose
scanned body is the *big-model* FEEL period: per-device gradients of the
``fed.train_step`` weighted-CE loss (the same loss ``make_train_step`` /
``make_multi_train_step`` scan — with ``compress=False`` the trajectory is
test-pinned equal to driving ``make_multi_train_step`` over the gathered
schedule batches), per-device SBC uploads through
:func:`repro.compression.sbc.sbc_uplink` (the pallas ``kernels/sbc.py``
composition on TPU, bitwise ``compress_dense`` on CPU), the eq. (1)
``B_k``-weighted aggregation, and the ``optim.sgd`` update applied through
the ``TrainState``/``apply_updates`` machinery.

Kernel dispatch follows the repo rule end to end: the runtime pins
``attn_impl="pallas"`` so attention runs ``kernels/flash_attention.py`` on
TPU and the test-covered jnp oracle on CPU, and ``mamba2_forward`` routes
its SSD scan through ``kernels.ops.ssd`` (pallas ``ssd_scan`` on TPU,
``ssd_reference`` on CPU).

The classification workload rides along unchanged: features are
deterministically quantized to token sequences (:func:`tokenize`), the
class label becomes the final next-token target, and test accuracy reads
the last position's argmax over the class-id slice of the vocab.
"""
from __future__ import annotations

from functools import lru_cache, partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.compression.sbc import sbc_uplink
from repro.configs.base import ArchConfig, SSMConfig
from repro.fed.engine import (EngineState, _normalize_active_batch,
                              _record_trace, _shard_batch_args,
                              assert_device_safe, enqueue, host_to_device,
                              stack_schedules)
from repro.fed.train_step import TrainState, make_loss_fn
from repro.models.model import Runtime, forward
from repro.models.model import init as model_init
from repro.optim import apply_updates, sgd

tree_map = jax.tree_util.tree_map

# tokenization constants: VOCAB feature bins (class ids live in the first
# N_CLASSES slots of the same vocab), sequences capped at SEQ_CAP tokens
SEQ_CAP = 16
VOCAB = 32
N_CLASSES = 10

# the kernel-dispatch runtime: "pallas" attention routes through
# kernels.ops.flash_attention, which falls back to the jnp ref on CPU
KERNEL_RT = Runtime(dtype=jnp.float32, attn_impl="pallas")


# ---------------------------------------------------------------------------
# spec (hidden, depth) -> ArchConfig per family
# ---------------------------------------------------------------------------


@lru_cache(maxsize=None)
def family_arch(model_family: str, hidden: int, depth: int) -> ArchConfig:
    """Derive the per-family architecture from the spec's (hidden, depth).

    ``hidden`` must be divisible by 4 (spec-validated): the transformer
    uses 4 query heads over ``hidden``, the SSM uses 8-wide state heads
    over ``2 * hidden`` inner channels.
    """
    if model_family == "transformer":
        return ArchConfig(
            name=f"feel-transformer-h{hidden}-d{depth}", family="dense",
            n_layers=depth, d_model=hidden, n_heads=4, n_kv_heads=2,
            d_ff=2 * hidden, vocab=VOCAB)
    if model_family == "mamba2":
        return ArchConfig(
            name=f"feel-mamba2-h{hidden}-d{depth}", family="ssm",
            n_layers=depth, d_model=hidden, n_heads=0, n_kv_heads=0,
            d_ff=0, vocab=VOCAB, attn_kind="none",
            ssm=SSMConfig(d_state=16, d_conv=4, expand=2, head_dim=8,
                          n_groups=1, chunk=4))
    raise ValueError(f"unknown big-model family {model_family!r}")


@lru_cache(maxsize=None)
def family_n_params(model_family: str, hidden: int, depth: int) -> int:
    """Cached true parameter count (prices the planner's uplink payload)."""
    return family_arch(model_family, hidden, depth).param_count()


def tokenize(data, seq_cap: int = SEQ_CAP, vocab: int = VOCAB):
    """Deterministic host-side feature quantization → (tokens, labels).

    Each example's first ``min(seq_cap, D)`` features (rounded down to a
    multiple of 4, so the SSD chunk size always divides the sequence) are
    squashed with tanh and binned into ``vocab`` ids (fixed affine map —
    no data-dependent statistics, so chunked re-tokenization is trivially
    bit-stable).  Labels are the next-token sequence with the class id as
    the final target, which is what makes last-position accuracy the
    classification metric.
    """
    x = np.asarray(data.x, np.float64)
    y = np.asarray(data.y)
    S = max(4, (min(seq_cap, x.shape[1]) // 4) * 4)
    if x.shape[1] < S:  # tiny feature dims: tile columns up to one chunk
        x = np.tile(x, (1, -(-S // x.shape[1])))
    bins = np.floor((np.tanh(x[:, :S] / 4.0) + 1.0) * 0.5 * vocab)
    tokens = np.clip(bins, 0, vocab - 1).astype(np.int64)
    labels = np.concatenate([tokens[:, 1:], y[:, None]], axis=1)
    return tokens, labels.astype(np.int64)


# ---------------------------------------------------------------------------
# the scanned period step (Steps 1-5 on the big-model train step)
# ---------------------------------------------------------------------------


def _model_period_step(cfg, rt, loss_fn, opt, compress, ratio,
                       tok, lab, test_tok, test_y, carry, xs):
    # named scopes as in engine._period_step (op_name metadata only)
    state, residual = carry
    idx, w, bk, lr = xs["idx"], xs["weight"], xs["batch"], xs["lr"]
    with jax.named_scope("grad"):
        # same active-mask invariant as engine._period_step: the schedule
        # already zeroes inactive users; multiplying keeps it for
        # hand-built schedules and is bitwise free for fully-active rows
        active = xs["active"]
        w = w * active[:, None]
        bk = bk * active
        t = tok[idx]                              # (K, slot, S)
        l_ = lab[idx]
        wt = jnp.broadcast_to(w[..., None], l_.shape).astype(jnp.float32)
        flat = {"tokens": t.reshape(-1, t.shape[-1]),
                "labels": l_.reshape(-1, l_.shape[-1]),
                "weights": wt.reshape(-1, wt.shape[-1])}
    with jax.named_scope("loss"):
        loss_before = loss_fn(state.params, flat)[1]

    # Step 1-2: per-device gradients of the weighted-CE train-step loss on
    # each device's own slot batch (surplus slots carry zero weight)
    def dev_grad_loss(p, tk, lk, wk):
        return loss_fn(p, {"tokens": tk, "labels": lk, "weights": wk})[0]

    with jax.named_scope("grad"):
        grads = jax.vmap(jax.grad(dev_grad_loss),
                         in_axes=(None, 0, 0, 0))(state.params, t, l_, wt)
    if compress:
        # per-device SBC with per-device error feedback — the kernel path
        # on accelerators, bitwise compress_dense on CPU (sbc_uplink)
        with jax.named_scope("sbc"):
            grads, residual = jax.vmap(
                lambda g, r: sbc_uplink(g, ratio, r))(grads, residual)
    # eq. (1): weighted average by B_k (padded rows carry B_k = 0); a
    # positive aggden fixes the denominator as in the MLP engine
    with jax.named_scope("aggregate"):
        den = xs["aggden"]
        wk = bk / jnp.where(den > 0, den, jnp.sum(bk))
        agg = tree_map(lambda g: jnp.tensordot(wk, g, axes=1), grads)
        updates, new_opt = opt.update(agg, state.opt, state.params, lr)
        params = apply_updates(state.params, updates)
        state = TrainState(params, new_opt, state.step + 1)

    with jax.named_scope("loss"):
        loss_after = loss_fn(params, flat)[1]
    with jax.named_scope("eval"):
        logits, _ = forward(cfg, params, test_tok, rt=rt)
        acc = jnp.mean((jnp.argmax(logits[:, -1, :N_CLASSES], axis=-1)
                        == test_y).astype(jnp.float32))
    return (state, residual), (loss_after, acc, loss_before - loss_after)


@lru_cache(maxsize=None)
def _model_trajectory_fn(model_family: str, hidden: int, depth: int,
                         compress: bool, ratio: float, batched: bool):
    key = (model_family, hidden, depth, compress, ratio, batched)
    cfg = family_arch(model_family, hidden, depth)
    rt = KERNEL_RT
    loss_fn = make_loss_fn(cfg, rt)
    opt = sgd()

    def run(params0, residual0, active, xs, tok, lab, test_tok, test_y):
        state0 = TrainState(params0, opt.init(params0),
                            jnp.zeros((), jnp.int32))
        step = partial(_model_period_step, cfg, rt, loss_fn, opt,
                       compress, ratio, tok, lab, test_tok, test_y)
        (state, residual), series = jax.lax.scan(
            step, (state0, residual0), dict(xs, active=active))
        return state.params, residual, series

    if batched:
        run = jax.vmap(run, in_axes=(0, 0, 0, 0, None, None, None, None))

    def model_trajectory(params0, residual0, active, xs, *data):
        # ledger entry OUTSIDE the vmap (same rationale as engine)
        _record_trace("model", key, (params0, residual0, active, xs, *data))
        return run(params0, residual0, active, xs, *data)

    return jax.jit(model_trajectory)


def model_trajectory_program(model_family: str, hidden: int, depth: int,
                             compress: bool = True, ratio: float = 0.005,
                             batched: bool = True):
    """The (cached) jitted big-model FEEL trajectory program.

    Public accessor for introspection — ``analysis``' probes call
    ``jax.make_jaxpr`` on this under ``suspend_trace_count``.
    """
    return _model_trajectory_fn(model_family, int(hidden), int(depth),
                                bool(compress), float(ratio), batched)


# ---------------------------------------------------------------------------
# batched drivers (mirror engine.run/resume_trajectory_batch)
# ---------------------------------------------------------------------------


def init_params_batch(model_family: str, hidden: int, depth: int, keys):
    """Stacked per-row model params: vmap of ``models.model.init`` over a
    (N, 2) uint32 key batch."""
    cfg = family_arch(model_family, hidden, depth)
    return jax.vmap(lambda k: model_init(cfg, k))(keys)


def run_model_trajectory_batch(params0, residual0,
                               schedules: Sequence, data, test, *,
                               model_family: str, hidden: int, depth: int,
                               compress: bool = True, ratio: float = 0.005,
                               mesh=None, active=None):
    """Batched big-model sweep: one program advances every (scenario, seed).

    Same contract as :func:`repro.fed.engine.run_trajectory_batch` —
    ``params0``/``residual0`` carry a leading batch axis, padded user rows
    ride the ``active`` mask — except the datasets enter as quantized
    token/label arrays (:func:`tokenize`).
    """
    with obs.upload("repro.dispatch.upload"):
        xs = stack_schedules(schedules)
        active = _normalize_active_batch(active, len(schedules),
                                         schedules[0].periods,
                                         schedules[0].idx.shape[1])
        tok, lab = tokenize(data)
        test_tok, _ = tokenize(test)
        data_args = host_to_device((tok, lab, test_tok, np.asarray(test.y)))
        if mesh is not None:
            (params0, residual0, active, xs), data_args = _shard_batch_args(
                mesh, (params0, residual0, active, xs), data_args)
    fn = _model_trajectory_fn(model_family, int(hidden), int(depth),
                              bool(compress), float(ratio), True)
    assert_device_safe((params0, residual0, active, xs, data_args),
                       "run_model_trajectory_batch")
    return enqueue(fn, params0, residual0, active, xs, *data_args)


def resume_model_trajectory_batch(state: EngineState,
                                  schedules: Sequence, data, test, *,
                                  model_family: str, hidden: int, depth: int,
                                  compress: bool = True, ratio: float = 0.005,
                                  mesh=None, active=None):
    """Advance a batched big-model trajectory by one schedule chunk
    (chunked-horizon counterpart of ``engine.resume_trajectory_batch``)."""
    params, residual, series = run_model_trajectory_batch(
        state.params, state.residual, schedules, data, test,
        model_family=model_family, hidden=hidden, depth=depth,
        compress=compress, ratio=ratio, mesh=mesh, active=active)
    return EngineState(params=params, residual=residual), series
