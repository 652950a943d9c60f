"""Big-model FEEL engine: transformer / Mamba-2 per-device train steps.

This is the ``model_family`` counterpart of :mod:`repro.fed.engine`'s
``feel_mlp`` scan.  A spec with ``model_family="transformer"`` or
``"mamba2"`` lowers to one jitted ``vmap(lax.scan)`` per bucket whose
scanned body (:func:`_model_period_step`) is the *big-model* FEEL period:
per-device gradients, per-device SBC uploads with error feedback through
:func:`repro.compression.sbc.sbc_uplink` (the pallas ``kernels/sbc.py``
composition on TPU, bitwise ``compress_dense`` on CPU), the eq. (1)
``B_k``-weighted aggregation, and the ``optim.sgd`` update applied through
the ``TrainState``/``apply_updates`` machinery.  It takes one of two forms:

* **A named architecture on token text** (``ScenarioSpec.arch``): the
  ``repro.configs`` ``ArchConfig`` with its first ``depth`` layers, at its
  published widths, trained on next-token prediction.  The dataset's ``x``
  is ``(N, seq_len + 1)`` token ids: a sequence's inputs are its first
  ``seq_len`` tokens and its targets its last ``seq_len``.  The loss is the
  token cross-entropy Σ w·nll / Σ w over every position, each sequence
  weighted by its schedule weight; test accuracy is top-1 next-token
  accuracy over every position.  So that a chip holds the model, the
  clients take their turns in a ``lax.scan`` (one client's gradient alive
  at a time, each adding its ``B_k``-weighted upload into the aggregate),
  every pass computes its logits in blocks of sequences of at most
  ``LOGIT_BLOCK_BYTES`` whose sums are divided once, and the layer scan
  runs under ``jax.checkpoint``.  A client outside the period's cohort or
  with ``B_k = 0`` adds nothing and keeps its residual.
* **The toy families on classification features** (no ``arch``): the
  architecture derives from the spec's ``(hidden, depth)``
  (:func:`family_arch`), features are deterministically quantized to token
  sequences (:func:`tokenize`) with the class label as the final
  next-token target, test accuracy reads the last position's argmax over
  the class-id slice of the vocab, and the clients' gradients are one
  ``vmap(grad)`` of the ``fed.train_step`` weighted-CE loss (with
  ``compress=False`` the trajectory is test-pinned equal to driving
  ``make_multi_train_step`` over the gathered schedule batches).

Kernel dispatch follows the repo rule end to end: the runtime pins
``attn_impl="pallas"`` so attention runs ``kernels/flash_attention.py`` on
TPU and the test-covered jnp oracle on CPU, and ``mamba2_forward`` routes
its SSD scan through ``kernels.ops.ssd`` (pallas ``ssd_scan`` on TPU at
the architecture's chunk, ``ssd_reference`` on CPU).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import lru_cache, partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.compression.sbc import sbc_uplink
from repro.configs import get_arch
from repro.configs.base import ArchConfig, SSMConfig
from repro.fed.engine import (EngineState, _normalize_active_batch,
                              _record_trace, _shard_batch_args,
                              assert_device_safe, enqueue, host_to_device,
                              stack_schedules)
from repro.fed.train_step import TrainState, make_loss_fn
from repro.models.layers import padded_vocab
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
# a named architecture's runtime: the same kernels, the layer scan
# rematerialized so the backward pass keeps one activation a layer
TEXT_RT = Runtime(dtype=jnp.float32, attn_impl="pallas", remat=True)

# the most float32 logits one pass of the token-text path holds at once
LOGIT_BLOCK_BYTES = 1 << 30


# ---------------------------------------------------------------------------
# the spec's architecture: named (arch, depth) or derived (hidden, depth)
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
def model_arch(model_family: str, hidden: int, depth: int,
               arch: Optional[str] = None) -> ArchConfig:
    """The architecture a spec trains: the named ``arch`` with its first
    ``depth`` layers kept, else :func:`family_arch`."""
    if arch is None:
        return family_arch(model_family, hidden, depth)
    return dataclasses.replace(get_arch(arch), n_layers=depth)


@lru_cache(maxsize=None)
def family_n_params(model_family: str, hidden: int, depth: int,
                    arch: Optional[str] = None) -> int:
    """Cached true parameter count (prices the planner's uplink payload)."""
    return model_arch(model_family, hidden, depth, arch).param_count()


def tokenize(data, seq_cap: int = SEQ_CAP, vocab: int = VOCAB):
    """Deterministic host-side feature quantization → (tokens, labels).

    Each example's first ``min(seq_cap, D)`` features (rounded down to a
    multiple of 4, so the SSD chunk size always divides the sequence) are
    squashed with tanh and binned into ``vocab`` ids (fixed affine map —
    no data-dependent statistics, so chunked re-tokenization is trivially
    bit-stable).  Labels are the next-token sequence with the class id as
    the final target, which is what makes last-position accuracy the
    classification metric.  Feature data only: a named architecture
    trains on token text (:func:`text_inputs`).
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


def text_inputs(data, arch: str, seq_len: int) -> np.ndarray:
    """The token text a named architecture trains on: ``data.x`` as
    ``(N, seq_len + 1)`` integer ids in ``[0, vocab)`` (checked here: the
    embedding's gather would clamp an id out of range silently)."""
    x = np.asarray(data.x)
    if x.dtype.kind not in "iu" or x.ndim != 2 or x.shape[1] != seq_len + 1:
        raise ValueError(
            f"arch={arch!r} trains on token text: the dataset's x must hold "
            f"integer ids of shape (N, seq_len + 1 = {seq_len + 1}), got "
            f"{x.dtype} {x.shape}")
    vocab = get_arch(arch).vocab
    if x.size and (x.min() < 0 or x.max() >= vocab):
        raise ValueError(
            f"arch={arch!r}: token ids must lie in [0, {vocab}), got "
            f"[{x.min()}, {x.max()}]")
    return x


def block_rows(n: int, item_bytes: int, block_bytes: int) -> int:
    """Items one pass over ``n`` takes at a time: all ``n`` where they fit
    in ``block_bytes``, else the largest divisor of ``n`` whose items fit
    (at least 1), so every block has the same shape and none is padded."""
    if n * item_bytes <= block_bytes:
        return n
    cap = max(1, block_bytes // item_bytes)
    return max(d for d in range(1, min(cap, n) + 1) if n % d == 0)


def seq_logit_bytes(cfg: ArchConfig, seq_len: int) -> int:
    """Bytes of one sequence's float32 logits over the padded vocabulary."""
    return 4 * seq_len * padded_vocab(cfg.vocab)


def logit_block(model_family: str, hidden: int, depth: int, arch: str,
                seq_len: int, slot: int) -> int:
    """Sequences of a client's gradient pass a logit block holds."""
    cfg = model_arch(model_family, hidden, depth, arch)
    return block_rows(slot, seq_logit_bytes(cfg, seq_len), LOGIT_BLOCK_BYTES)


def _in_blocks(fn, arrays, rows: int):
    """Σ over blocks of ``rows`` leading items of ``fn(block)`` (a pytree
    of sums), one block at a time in a ``lax.scan``; ``fn(arrays)`` where
    one block holds them all."""
    n = jax.tree_util.tree_leaves(arrays)[0].shape[0]
    if rows == n:
        return fn(arrays)
    blocks = tree_map(lambda a: a.reshape((n // rows, rows) + a.shape[1:]),
                      arrays)
    zero = tree_map(lambda a: jnp.zeros(a.shape, a.dtype), jax.eval_shape(
        fn, tree_map(lambda a: a[0], blocks)))

    def body(acc, blk):
        return tree_map(jnp.add, acc, fn(blk)), None

    return jax.lax.scan(body, zero, blocks)[0]


# ---------------------------------------------------------------------------
# the scanned period step (Steps 1-5 on the big-model train step)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class _Task:
    """What a bucket's period step is built from (static per program)."""
    cfg: ArchConfig
    rt: Runtime
    compress: bool
    ratio: float
    seq_len: Optional[int] = None      # token text (a named architecture)
    block_bytes: int = LOGIT_BLOCK_BYTES


def _model_period_step(task: _Task, data, carry, xs):
    """One scanned big-model FEEL period: ``carry`` is (``TrainState``,
    per-client residuals), ``xs`` the period's schedule with its
    ``active`` mask; returns the new carry and (loss after the update,
    test accuracy, loss before − after)."""
    if task.seq_len is not None:
        return _text_period(task, data, carry, xs)
    return _feature_period(task, data, carry, xs)


def _text_period(task: _Task, data, carry, xs):
    """The named architecture on token text, clients in turn, logits in
    blocks (module docstring)."""
    x, test_x = data
    cfg, rt, opt = task.cfg, task.rt, sgd()
    state, residual = carry
    active = xs["active"]
    idx = xs["idx"]                                   # (K, slot)
    w = xs["weight"] * active[:, None]
    bk = xs["batch"] * active
    k_pad, slot = idx.shape
    seq_bytes = seq_logit_bytes(cfg, task.seq_len)

    def nll_sums(params, seqs, wts):
        # (Σ w·nll, Σ w) over every position of (B, S + 1) token rows
        logits, _ = forward(cfg, params, seqs[:, :-1], rt=rt)
        lab = seqs[:, 1:]
        nll = (jax.nn.logsumexp(logits, -1)
               - jnp.take_along_axis(logits, lab[..., None], -1)[..., 0])
        wt = jnp.broadcast_to(wts[:, None], nll.shape).astype(nll.dtype)
        return jnp.sum(nll * wt), jnp.sum(wt)

    def loss_of(params):
        ids, wts = idx.reshape(-1), w.reshape(-1)
        s, n = _in_blocks(lambda b: nll_sums(params, x[b[0]], b[1]),
                          (ids, wts), block_rows(ids.shape[0], seq_bytes,
                                                 task.block_bytes))
        return s / jnp.maximum(n, 1e-6)

    def client_grad(params, ids, wts):
        # Σ over blocks of ∇(Σ_block w·nll), divided once by Σ w
        def block(b):
            return jax.grad(lambda p: nll_sums(p, x[b[0]], b[1]),
                            has_aux=True)(params)

        g, n = _in_blocks(block, (ids, wts),
                          block_rows(slot, seq_bytes, task.block_bytes))
        return tree_map(lambda a: a / jnp.maximum(n, 1e-6), g)

    with jax.named_scope("loss"):
        loss_before = loss_of(state.params)

    den = xs["aggden"]
    coef = bk / jnp.where(den > 0, den, jnp.sum(bk))
    takes_part = (active > 0) & (bk > 0)

    # Steps 1-3 one client at a time: its gradient, its SBC upload with
    # error feedback, its B_k-weighted share of the aggregate
    def client(c, inp):
        agg, residual = c
        k, ids, wts, ck, part = inp
        with jax.named_scope("grad"):
            g = client_grad(state.params, ids, wts)
        if task.compress:
            with jax.named_scope("sbc"):
                old = tree_map(lambda r: r[k], residual)
                g, new = sbc_uplink(g, task.ratio, old)
                residual = tree_map(
                    lambda r, a, b: r.at[k].set(jnp.where(part, a, b)),
                    residual, new, old)
        with jax.named_scope("aggregate"):
            agg = tree_map(lambda a, s: a + ck * s, agg, g)
        return (agg, residual), None

    zero = tree_map(jnp.zeros_like, state.params)
    (agg, residual), _ = jax.lax.scan(
        client, (zero, residual),
        (jnp.arange(k_pad), idx, w, coef, takes_part))
    with jax.named_scope("aggregate"):
        updates, new_opt = opt.update(agg, state.opt, state.params,
                                      xs["lr"])
        params = apply_updates(state.params, updates)
        state = TrainState(params, new_opt, state.step + 1)

    with jax.named_scope("loss"):
        loss_after = loss_of(params)
    with jax.named_scope("eval"):
        def hits(seqs):
            logits, _ = forward(cfg, params, seqs[:, :-1], rt=rt)
            right = jnp.argmax(logits, -1) == seqs[:, 1:]
            return (jnp.sum(right.astype(jnp.float32)),
                    jnp.asarray(right.size, jnp.float32))

        right, made = _in_blocks(hits, test_x, block_rows(
            test_x.shape[0], seq_bytes, task.block_bytes))
        acc = right / made
    return (state, residual), (loss_after, acc, loss_before - loss_after)


def _feature_period(task: _Task, data, carry, xs):
    """The toy family on tokenized classification features: every
    client's gradient in one ``vmap(grad)`` (module docstring)."""
    # named scopes as in engine._period_step (op_name metadata only)
    tok, lab, test_tok, test_y = data
    cfg, rt, opt = task.cfg, task.rt, sgd()
    loss_fn = make_loss_fn(cfg, rt)
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
    if task.compress:
        # per-device SBC with per-device error feedback — the kernel path
        # on accelerators, bitwise compress_dense on CPU (sbc_uplink)
        with jax.named_scope("sbc"):
            grads, residual = jax.vmap(
                lambda g, r: sbc_uplink(g, task.ratio, r))(grads, residual)
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
                         compress: bool, ratio: float, batched: bool,
                         arch: Optional[str] = None,
                         seq_len: Optional[int] = None,
                         block_bytes: int = LOGIT_BLOCK_BYTES):
    key = (model_family, hidden, depth, compress, ratio, batched, arch,
           seq_len, block_bytes)
    cfg = model_arch(model_family, hidden, depth, arch)
    task = _Task(cfg=cfg, rt=KERNEL_RT if arch is None else TEXT_RT,
                 compress=compress, ratio=ratio, seq_len=seq_len,
                 block_bytes=block_bytes)
    opt = sgd()

    def run(params0, residual0, active, xs, *data):
        state0 = TrainState(params0, opt.init(params0),
                            jnp.zeros((), jnp.int32))
        step = partial(_model_period_step, task, data)
        (state, residual), series = jax.lax.scan(
            step, (state0, residual0), dict(xs, active=active))
        return state.params, residual, series

    if batched:
        n_data = 4 if arch is None else 2
        run = jax.vmap(run, in_axes=(0, 0, 0, 0) + (None,) * n_data)

    def model_trajectory(params0, residual0, active, xs, *data):
        # ledger entry OUTSIDE the vmap (same rationale as engine)
        _record_trace("model", key, (params0, residual0, active, xs, *data))
        return run(params0, residual0, active, xs, *data)

    # a named architecture's weights and residuals are the bulk of the
    # chip's memory: the program updates them in place
    return jax.jit(model_trajectory,
                   donate_argnums=() if arch is None else (0, 1))


def model_trajectory_program(model_family: str, hidden: int, depth: int,
                             compress: bool = True, ratio: float = 0.005,
                             batched: bool = True,
                             arch: Optional[str] = None,
                             seq_len: Optional[int] = None):
    """The (cached) jitted big-model FEEL trajectory program.

    Public accessor for introspection — ``analysis``' probes call
    ``jax.make_jaxpr`` on this under ``suspend_trace_count``.
    """
    return _model_trajectory_fn(model_family, int(hidden), int(depth),
                                bool(compress), float(ratio), batched, arch,
                                seq_len, LOGIT_BLOCK_BYTES)


# ---------------------------------------------------------------------------
# batched drivers (mirror engine.run/resume_trajectory_batch)
# ---------------------------------------------------------------------------


def init_params_batch(model_family: str, hidden: int, depth: int, keys,
                      arch: Optional[str] = None):
    """Stacked per-row model params: vmap of ``models.model.init`` over a
    (N, 2) uint32 key batch."""
    cfg = model_arch(model_family, hidden, depth, arch)
    return jax.vmap(lambda k: model_init(cfg, k))(keys)


def program_data(data, test, arch: Optional[str] = None,
                 seq_len: Optional[int] = None) -> tuple:
    """The host datasets as the program takes them: token text for a
    named architecture, else the quantized features (:func:`tokenize`)."""
    if arch is not None:
        return (text_inputs(data, arch, seq_len),
                text_inputs(test, arch, seq_len))
    tok, lab = tokenize(data)
    test_tok, _ = tokenize(test)
    return tok, lab, test_tok, np.asarray(test.y)


def run_model_trajectory_batch(params0, residual0,
                               schedules: Sequence, data, test, *,
                               model_family: str, hidden: int, depth: int,
                               compress: bool = True, ratio: float = 0.005,
                               mesh=None, active=None,
                               arch: Optional[str] = None,
                               seq_len: Optional[int] = None):
    """Batched big-model sweep: one program advances every (scenario, seed).

    Same contract as :func:`repro.fed.engine.run_trajectory_batch` —
    ``params0``/``residual0`` carry a leading batch axis, padded user rows
    ride the ``active`` mask — except the datasets enter as the program
    takes them (:func:`program_data`).  With ``arch`` the program consumes
    (donates) ``params0`` and ``residual0``.
    """
    with obs.upload("repro.dispatch.upload"):
        xs = stack_schedules(schedules)
        active = _normalize_active_batch(active, len(schedules),
                                         schedules[0].periods,
                                         schedules[0].idx.shape[1])
        args = host_to_device(program_data(data, test, arch, seq_len))
        if mesh is not None:
            (params0, residual0, active, xs), args = _shard_batch_args(
                mesh, (params0, residual0, active, xs), args)
    fn = _model_trajectory_fn(model_family, int(hidden), int(depth),
                              bool(compress), float(ratio), True, arch,
                              seq_len, LOGIT_BLOCK_BYTES)
    assert_device_safe((params0, residual0, active, xs, args),
                       "run_model_trajectory_batch")
    return enqueue(fn, params0, residual0, active, xs, *args)


def resume_model_trajectory_batch(state: EngineState,
                                  schedules: Sequence, data, test, *,
                                  model_family: str, hidden: int, depth: int,
                                  compress: bool = True, ratio: float = 0.005,
                                  mesh=None, active=None,
                                  arch: Optional[str] = None,
                                  seq_len: Optional[int] = None):
    """Advance a batched big-model trajectory by one schedule chunk
    (chunked-horizon counterpart of ``engine.resume_trajectory_batch``)."""
    params, residual, series = run_model_trajectory_batch(
        state.params, state.residual, schedules, data, test,
        model_family=model_family, hidden=hidden, depth=depth,
        compress=compress, ratio=ratio, mesh=mesh, active=active,
        arch=arch, seq_len=seq_len)
    return EngineState(params=params, residual=residual), series
