"""The plain FEEL round and the comparison that decides ``correct``.

The reference imports nothing of the program.  For each compared row it
makes the row's initial weights from the row seed, reads the row's host
plan (which examples each client draws, its B_k, the period's learning
rate and the participating cohort: the traffic the device ran), and
trains period by period in plain ``jax.numpy``:

1. each participating client k takes the gradient of its weighted loss
   on its own examples;
2. with compression on, SBC with error feedback: acc = g_k + r_k; keep
   the entries of |acc| at or above its ⌈r·n⌋-th largest value (exact, by
   sorting), keep the sign group with the larger magnitude sum, send that
   group at its mean magnitude; r_k ← acc − sent.  A client outside the
   period's cohort computes and sends nothing, and its residual waits;
3. eq. (1): the server averages the uploads weighted by B_k / Σ B_k (or
   by a fixed positive denominator, where the plan gives one);
4. SGD: θ ← θ − η·aggregate;
5. the loss after the update over the cohort's examples, and the test
   accuracy.

``dtype`` float32 computes at ``highest`` matmul precision (the
reference); bfloat16 keeps weights, residuals and every intermediate in
bfloat16 (the control).

The plain model is the file under ``models/`` that the configuration's
``reference`` names, or ``models/<model_family>.py`` where it names none
(``config_module``).  It gives ``init``, ``train_inputs``,
``test_inputs``, ``nll_sums`` (Σ w·nll and Σ w of a batch),
``weighted_mean``, ``hits`` (correct predictions and predictions made),
``logits_per_example`` and ``train_flops_per_example``.  A pass that
would hold more than ``BLOCK_BYTES`` is computed in blocks whose sums
are divided once (``make_trajectory``); at the sizes where every pass
fits, nothing is blocked.
"""
from __future__ import annotations

import importlib.util
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

MODELS_DIR = Path(__file__).resolve().parent / "models"
tree_map = jax.tree_util.tree_map

# the most one pass of the reference holds at once, by ``make_trajectory``'s
# blocking rules; a pass that fits runs whole
BLOCK_BYTES = 1 << 30
F32 = 4


def model_module(name: str, models_dir: Path = MODELS_DIR):
    """The reference model ``<models_dir>/<name>.py``."""
    path = models_dir / f"{name}.py"
    if not path.is_file():
        raise ValueError(f"no reference model {path}")
    spec = importlib.util.spec_from_file_location(f"bench_model_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def config_module(cfg: dict, models_dir: Path = MODELS_DIR):
    """The reference model a configuration names by ``reference``, or by
    its ``model_family`` where it names none."""
    return model_module(cfg.get("reference") or cfg["model_family"],
                        models_dir)


def block_rows(n: int, item_bytes: int, block_bytes: int) -> int:
    """Items one pass over ``n`` takes at a time: all ``n`` where they fit
    in ``block_bytes``, else the largest divisor of ``n`` whose items fit
    (at least 1), so that every block has the same shape and none is
    padded."""
    if n * item_bytes <= block_bytes:
        return n
    cap = max(1, block_bytes // item_bytes)
    return max(d for d in range(1, min(cap, n) + 1) if n % d == 0)


def in_blocks(fn, arrays, rows: int):
    """Σ over blocks of ``rows`` leading items of ``fn(block)`` (a pytree
    of sums), one block at a time; ``fn(arrays)`` where one block holds
    them all."""
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


@dataclass(frozen=True)
class Blocks:
    """How ``make_trajectory`` splits each pass of a period."""
    in_turn: bool       # the cohort's gradients one client at a time
    client_rows: int    # examples of one client's gradient a block
    loss_rows: int      # examples of the loss pass after the update a block
    test_rows: int      # examples of the test-accuracy pass a block


def blocks_for(ex_bytes: int, cohort: int, slot: int, n_params: int,
               n_test: int, block_bytes: int = BLOCK_BYTES) -> Blocks:
    """The blocking rules, from the sizes a trajectory sees: ``ex_bytes``
    of logits an example, ``cohort`` clients a period of ``slot`` examples
    each, ``n_params`` float32 parameters, ``n_test`` test examples."""
    return Blocks(in_turn=cohort * n_params * F32 > block_bytes,
                  client_rows=block_rows(slot, ex_bytes, block_bytes),
                  loss_rows=block_rows(cohort * slot, ex_bytes, block_bytes),
                  test_rows=block_rows(n_test, ex_bytes, block_bytes))


def sbc(t, ratio: float):
    """Sparse binary compression of one tensor (exact top-k threshold)."""
    flat = t.reshape(-1)
    n = flat.shape[0]
    k = max(1, int(round(n * ratio)))
    mag = jnp.abs(flat)
    thr = jnp.sort(mag)[n - k]
    keep = mag >= thr
    pos = keep & (flat > 0)
    neg = keep & (flat < 0)
    zero = jnp.zeros((), flat.dtype)
    pos_sum = jnp.sum(jnp.where(pos, mag, zero))
    neg_sum = jnp.sum(jnp.where(neg, mag, zero))
    use_pos = pos_sum >= neg_sum
    grp = jnp.where(use_pos, pos, neg)
    mean = (jnp.where(use_pos, pos_sum, neg_sum)
            / jnp.maximum(jnp.sum(grp), 1).astype(flat.dtype))
    out = jnp.where(grp, jnp.where(use_pos, mean, -mean), zero)
    return out.reshape(t.shape)


def cohorts(active: np.ndarray) -> np.ndarray:
    """(P, S) indices of each period's participants (S fixed per row)."""
    counts = (active > 0).sum(1)
    if not (counts == counts[0]).all():
        raise ValueError(f"cohort size varies over periods: {counts}")
    return np.stack([np.flatnonzero(a > 0) for a in active]).astype(np.int32)


def make_trajectory(model, cfg: dict, ratio: float, compress: bool,
                    dtype, block_bytes: int = BLOCK_BYTES):
    """A jitted ``(params0, plan, train, test) → (losses, accs, params,
    first_agg_norms)`` of one row.

    Each pass is computed in blocks where it would not fit in
    ``block_bytes`` (``blocks_for``, by the sizes the trajectory sees and
    ``model.logits_per_example`` float32 logits an example):

    * the cohort's gradients are taken all at once (``vmap``) where the S
      clients' gradients fit, else one client at a time, each client's
      SBC and residual update in turn and the B_k-weighted sum carried;
    * a client's gradient is one pass where its examples' logits fit, else
      Σ over blocks of ∇(Σ w·nll), divided once by Σ w;
    * the loss after the update and the test accuracy sum the weighted
      nll and the correct predictions over blocks of examples where their
      logits do not fit, and divide once.
    """
    prec = (jax.lax.Precision.HIGHEST if dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)
    ex_bytes = F32 * int(model.logits_per_example(cfg))

    def loss_of(params, batch, w):
        return model.weighted_mean(*model.nll_sums(params, batch, w, cfg,
                                                   prec))

    def client_grad(params, batch, w, rows):
        if rows == w.shape[0]:
            return jax.grad(loss_of)(params, batch, w)

        def block(blk):
            b, wb = blk
            return jax.grad(lambda p: model.nll_sums(p, b, wb, cfg, prec),
                            has_aux=True)(params)

        g, n = in_blocks(block, (batch, w), rows)
        return tree_map(lambda a: model.weighted_mean(a, n), g)

    def cohort_all(params, residual, ids, batch, w, c, rows):
        grads = jax.vmap(lambda b, wk: client_grad(params, b, wk, rows))(
            batch, w)
        if compress:
            acc = tree_map(lambda g, r: g + r[ids], grads, residual)
            grads = tree_map(
                lambda a: jax.vmap(lambda t: sbc(t, ratio))(a), acc)
            residual = tree_map(lambda r, a, s: r.at[ids].set(a - s),
                                residual, acc, grads)
        agg = tree_map(lambda g: jnp.tensordot(c, g, axes=1), grads)
        return agg, residual

    def cohort_in_turn(params, residual, ids, batch, w, c, rows):
        def client(carry, xs):
            agg, residual = carry
            k, b, wk, ck = xs
            g = client_grad(params, b, wk, rows)
            if compress:
                acc = tree_map(lambda a, r: a + r[k], g, residual)
                g = tree_map(lambda a: sbc(a, ratio), acc)
                residual = tree_map(lambda r, a, s: r.at[k].set(a - s),
                                    residual, acc, g)
            return (tree_map(lambda s, a: s + ck * a, agg, g),
                    residual), None

        zero = tree_map(jnp.zeros_like, params)
        return jax.lax.scan(client, (zero, residual), (ids, batch, w, c))[0]

    def period(carry, xs, train, test, blocks):
        params, residual = carry
        ids = xs["cohort"]                               # (S,)
        idx = xs["idx"][ids]                             # (S, slot)
        w = xs["weight"][ids].astype(dtype)
        bk = xs["batch"][ids].astype(dtype)
        batch = tuple(a[idx] for a in train)
        den = jnp.where(xs["aggden"] > 0, xs["aggden"].astype(dtype),
                        jnp.sum(bk))
        cohort = cohort_in_turn if blocks.in_turn else cohort_all
        agg, residual = cohort(params, residual, ids, batch, w, bk / den,
                               blocks.client_rows)
        params = tree_map(lambda p, g: p - xs["lr"].astype(dtype) * g,
                          params, agg)
        flat = tuple(a.reshape((-1,) + a.shape[2:]) for a in batch)
        loss = model.weighted_mean(*in_blocks(
            lambda b: model.nll_sums(params, b[0], b[1], cfg, prec),
            (flat, w.reshape(-1)), blocks.loss_rows))
        right, made = in_blocks(lambda b: model.hits(params, b, cfg, prec),
                                test, blocks.test_rows)
        norms = tree_map(lambda g: jnp.sqrt(jnp.sum(
            jnp.square(g.astype(jnp.float32)))), agg)
        return (params, residual), (loss.astype(jnp.float32),
                                    (right / made).astype(jnp.float32),
                                    norms)

    @jax.jit
    def run(params0, plan, train, test):
        params0 = tree_map(lambda a: a.astype(dtype), params0)
        k = plan["batch"].shape[1]
        blocks = blocks_for(
            ex_bytes, plan["cohort"].shape[1], plan["idx"].shape[-1],
            sum(p.size for p in jax.tree_util.tree_leaves(params0)),
            jax.tree_util.tree_leaves(test)[0].shape[0], block_bytes)
        residual = (tree_map(lambda p: jnp.zeros((k,) + p.shape, dtype),
                             params0) if compress else None)
        (params, _), (losses, accs, norms) = jax.lax.scan(
            lambda c, x: period(c, x, train, test, blocks),
            (params0, residual), plan)
        first = tree_map(lambda n: n[0], norms)
        return losses, accs, tree_map(lambda a: a.astype(jnp.float32),
                                      params), first

    return run


def row_plan(arrays: dict, row: int) -> dict:
    """One row's per-period scan inputs from ``workload.plan_arrays``."""
    return {
        "cohort": jnp.asarray(cohorts(arrays["active"][row])),
        "idx": jnp.asarray(arrays["idx"][row].astype(np.int32)),
        "weight": jnp.asarray(arrays["weight"][row], jnp.float32),
        "batch": jnp.asarray(arrays["batch"][row], jnp.float32),
        "lr": jnp.asarray(arrays["lr"][row], jnp.float32),
        "aggden": jnp.asarray(arrays["aggden"][row], jnp.float32),
    }


def leaves_by_path(tree) -> Dict[str, np.ndarray]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(p): np.asarray(v, np.float64)
            for p, v in flat}


def change_gaps(init: dict, prog: dict, ref: dict, first: dict,
                exclude_share: float = 1e-3) -> Dict[str, float]:
    """Per leaf: the gap between the program's and the reference's norm of
    the parameters' change over the run, over the reference's norm of that
    leaf or of the median leaf, whichever is larger.  Leaves whose first
    aggregated gradient in the reference is under ``exclude_share`` of the
    median leaf's are left out (they move by round-off alone)."""
    d_ref = {k: float(np.linalg.norm(ref[k] - init[k])) for k in init}
    d_prog = {k: float(np.linalg.norm(prog[k] - init[k])) for k in init}
    g_med = float(np.median(list(first.values())))
    d_med = float(np.median(list(d_ref.values())))
    out = {}
    for k in init:
        if first[k] < exclude_share * g_med:
            continue
        out[k] = abs(d_prog[k] - d_ref[k]) / max(d_ref[k], d_med, 1e-30)
    return out


def ledger_faults(arrays: dict, parts: List[List[np.ndarray]],
                  results_times: np.ndarray, results_gb: np.ndarray,
                  b_max: int, cohort_size: int, policies: List[str],
                  base_lr: float, ref_batch: float) -> List[str]:
    """Checks of the host ledger that need no channel draws: the plan's
    per-client batches, weights, sample indices, cohort sizes, simulated
    clock and global batch agree with each other, with each client's data
    partition, and with what ``Experiment.run`` returned; the learning
    rate follows the paper's scaling law η = η₀·√(Σ B_k / B_ref) (§III-A)
    in every period; the fixed policies choose B_k = B_max (``full``) and
    B_k = 1 (``online``) for every participant.  Algorithm 1's choices
    (``proposed``) and the ``random`` draws depend on the program's
    channel and random draws, so only their range is checked."""
    bad = []
    act = arrays["active"] > 0
    batch = arrays["batch"]
    n, periods, _ = batch.shape
    if not np.array_equal(results_times, arrays["times"]):
        bad.append("Results.times differ from the plan's clock")
    if not np.array_equal(results_gb, arrays["global_batch"]):
        bad.append("Results.global_batch differs from the plan")
    for r in range(n):
        t = arrays["times"][r]
        if not (np.all(np.isfinite(t)) and t[0] > 0
                and np.all(np.diff(t) > 0)):
            bad.append(f"row {r}: simulated clock not increasing")
        gb = (batch[r] * act[r]).sum(1)
        if not np.array_equal(gb, arrays["global_batch"][r]):
            bad.append(f"row {r}: global batch != sum of B_k")
        if np.any(act[r].sum(1) != cohort_size):
            bad.append(f"row {r}: cohort size != {cohort_size}")
        bk = batch[r]
        want_lr = base_lr * np.sqrt(gb / ref_batch)
        if not np.allclose(arrays["lr"][r], want_lr, rtol=1e-6, atol=0):
            bad.append(f"row {r}: learning rate off the scaling law")
        fixed = {"full": b_max, "online": 1}.get(policies[r])
        if fixed is not None and np.any(bk[act[r]] != fixed):
            bad.append(f"row {r}: {policies[r]} policy with B_k != {fixed}")
        if np.any(bk[act[r]] < 1) or np.any(bk[act[r]] > b_max):
            bad.append(f"row {r}: participant B_k outside [1, {b_max}]")
        if np.any(bk[~act[r]] != 0):
            bad.append(f"row {r}: non-participant with B_k > 0")
        slot = arrays["weight"].shape[-1]
        want = (np.arange(slot)[None, None, :] < bk[..., None])
        if not np.array_equal(arrays["weight"][r] > 0, want):
            bad.append(f"row {r}: example weights do not realize B_k")
        for k in range(bk.shape[1]):
            if k >= len(parts[r]):
                continue
            used = arrays["idx"][r][:, k][want[:, k]]
            if not np.isin(used, parts[r][k]).all():
                bad.append(f"row {r}: client {k} drew outside its data")
                break
    return bad


def partition(kind: str, labels: np.ndarray, k: int, seed: int):
    """The paper's §VI-A split: IID, a seeded permutation cut into k equal
    parts; non-IID, examples sorted by label, cut into 2k shards, two
    seeded shards per client."""
    rng = np.random.default_rng(seed)
    if kind == "iid":
        return [np.sort(p) for p in
                np.array_split(rng.permutation(len(labels)), k)]
    shards = np.array_split(np.argsort(labels, kind="stable"), 2 * k)
    assign = rng.permutation(2 * k)
    return [np.sort(np.concatenate([shards[assign[2 * i]],
                                    shards[assign[2 * i + 1]]]))
            for i in range(k)]
