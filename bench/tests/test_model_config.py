"""A model configuration enters the benchmark by files alone: the three
optional configuration keys (``spec``, ``data``, ``reference``), token
text drawn from the seed, and the reference computed in blocks.

Where the three keys are absent, the harness computes bitwise what it
computed before they existed: ``frozen_*`` below are copies of those
functions, kept here as the yardstick.
"""
import json
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import judge
import reference
import workload
from conftest import BENCH, TINY, TOY_LM

HIGHEST = jax.lax.Precision.HIGHEST
tree_map = jax.tree_util.tree_map


# ---------------------------------------------------------------------------
# frozen copies of the harness before the configuration keys
# ---------------------------------------------------------------------------


def frozen_make_data(config, seed):
    n_train, n_test = int(config["n_train"]), int(config["n_test"])
    dim, classes = int(config["input_dim"]), int(config["classes"])
    spread = float(config["spread"])
    (s,) = workload.derived_seeds(seed, 1, workload.STREAM_DATA)

    @jax.jit
    def draw(key):
        kc, ky, kx = jax.random.split(key, 3)
        centers = jax.random.normal(kc, (classes, dim)) * (spread
                                                           / dim ** 0.5)
        y = jax.random.randint(ky, (n_train + n_test,), 0, classes)
        x = centers[y] + jax.random.normal(kx, (n_train + n_test, dim))
        return x, y.astype(jnp.int32)

    x, y = jax.device_get(draw(jax.random.key(s)))
    return np.asarray(x), np.asarray(y)


def frozen_make_specs(config, traffic, seed):
    from repro.api import ScenarioSpec
    from repro.topology import Sampling

    fleet = workload.make_fleet(traffic["fleet"])
    n_seeds = int(traffic["seeds_per_spec"])
    sampling = (None if traffic.get("sampling") is None
                else Sampling(**traffic["sampling"]))
    cells = [(part, pol) for part in traffic["partitions"]
             for pol in traffic["policies"]]
    row_seeds = workload.derived_seeds(seed, n_seeds * len(cells),
                                       workload.STREAM_ROWS)
    return [ScenarioSpec(
        fleet=fleet, name=traffic["fleet"]["name"], partition=part,
        policy=pol, compress=bool(traffic["compress"]),
        compression=float(config["compression"]),
        b_max=int(config["b_max"]), base_lr=float(traffic["base_lr"]),
        seeds=tuple(row_seeds[i * n_seeds:(i + 1) * n_seeds]),
        hidden=int(config["hidden"]), depth=int(config["depth"]),
        sampling=sampling, model_family=config["model_family"])
        for i, (part, pol) in enumerate(cells)]


def frozen_mlp_loss(params, batch, w, cfg, prec):
    x, y = batch
    z = reference.model_module("feel_mlp").logits(params, x, prec)
    nll = jax.nn.logsumexp(z, axis=-1) - jnp.take_along_axis(
        z, y[:, None], axis=1)[:, 0]
    return jnp.sum(nll * w) / jnp.maximum(jnp.sum(w), 1e-9)


def frozen_mlp_accuracy(params, batch, cfg, prec):
    x, y = batch
    z = reference.model_module("feel_mlp").logits(params, x, prec)
    return jnp.mean((jnp.argmax(z, -1) == y).astype(jnp.float32))


def frozen_make_trajectory(model, cfg, ratio, compress, dtype):
    prec = HIGHEST if dtype == jnp.float32 else jax.lax.Precision.DEFAULT
    sbc = reference.sbc

    def client_grad(params, batch, w):
        return jax.grad(frozen_mlp_loss)(params, batch, w, cfg, prec)

    def period(carry, xs, train, test):
        params, residual = carry
        ids = xs["cohort"]
        idx = xs["idx"][ids]
        w = xs["weight"][ids].astype(dtype)
        bk = xs["batch"][ids].astype(dtype)
        batch = tuple(a[idx] for a in train)
        grads = jax.vmap(client_grad, in_axes=(None, 0, 0))(params, batch,
                                                            w)
        if compress:
            acc = tree_map(lambda g, r: g + r[ids], grads, residual)
            grads = tree_map(
                lambda a: jax.vmap(lambda t: sbc(t, ratio))(a), acc)
            residual = tree_map(lambda r, a, s: r.at[ids].set(a - s),
                                residual, acc, grads)
        den = jnp.where(xs["aggden"] > 0, xs["aggden"].astype(dtype),
                        jnp.sum(bk))
        agg = tree_map(lambda g: jnp.tensordot(bk / den, g, axes=1), grads)
        params = tree_map(lambda p, g: p - xs["lr"].astype(dtype) * g,
                          params, agg)
        flat = tuple(a.reshape((-1,) + a.shape[2:]) for a in batch)
        loss = frozen_mlp_loss(params, flat, w.reshape(-1), cfg, prec)
        acc_ = frozen_mlp_accuracy(params, test, cfg, prec)
        norms = tree_map(lambda g: jnp.sqrt(jnp.sum(
            jnp.square(g.astype(jnp.float32)))), agg)
        return (params, residual), (loss.astype(jnp.float32),
                                    acc_.astype(jnp.float32), norms)

    @jax.jit
    def run(params0, plan, train, test):
        params0 = tree_map(lambda a: a.astype(dtype), params0)
        k = plan["batch"].shape[1]
        residual = tree_map(lambda p: jnp.zeros((k,) + p.shape, dtype),
                            params0)
        (params, _), (losses, accs, norms) = jax.lax.scan(
            lambda c, x: period(c, x, train, test), (params0, residual),
            plan)
        first = tree_map(lambda n: n[0], norms)
        return losses, accs, tree_map(lambda a: a.astype(jnp.float32),
                                      params), first

    return run


def _mlp_config(**over):
    cfg = json.loads((BENCH / "configs" / "feel_mlp.json").read_text())
    cfg.update(over)
    return cfg


def _traffic(name="fig45_gpu6"):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


# ---------------------------------------------------------------------------
# the MLP configuration: bitwise what it was
# ---------------------------------------------------------------------------


def test_mlp_specs_and_data_are_unchanged():
    cfg = _mlp_config()
    assert not {"spec", "data", "reference"} & set(cfg)
    for seed in (5, 2**33 + 1):
        assert (workload.make_specs(cfg, _traffic(), seed)
                == frozen_make_specs(cfg, _traffic(), seed))
    tiny = _mlp_config(**TINY["feel_mlp"])
    train, test = workload.make_data(tiny, 2**32 + 9)
    x, y = frozen_make_data(tiny, 2**32 + 9)
    n = tiny["n_train"]
    for got, want in ((train.x, x[:n]), (train.y, y[:n]),
                      (test.x, x[n:]), (test.y, y[n:])):
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(got, want)


def test_judge_numbers_are_bitwise_the_frozen_reference(tiny_root,
                                                        monkeypatch):
    """A tiny fig45 cell through the program once, then judged by today's
    reference and by the frozen one: every number and every period's gap
    is the same float."""
    from repro.api import Experiment

    root = tiny_root("feel_mlp.fig45_gpu6")
    cell = workload.find_cell(root, "feel_mlp.fig45_gpu6", root / "bench")
    train, test = workload.make_data(cell.config, 33)
    spans = workload.Spans()
    res = Experiment(train, test, workload.make_specs(
        cell.config, cell.traffic, 33)).run(
            int(cell.traffic["periods"]),
            executor=workload.make_executor(cell.traffic["executor"]))
    judged = judge.Judge(cell, spans.records, res, 33, train.y)
    now = judged.numbers(train, test)
    now_by = dict(judged.by_period)
    monkeypatch.setattr(reference, "make_trajectory",
                        frozen_make_trajectory)
    then = judged.numbers(train, test)
    assert now == then
    assert now_by == judged.by_period


def test_fig45_sizes_take_the_single_pass():
    """At fig45's full sizes (6 clients of 128 examples, 855,050
    parameters, 10,000 test rows, 10 logits an example) no pass is
    blocked."""
    cfg, tr = _mlp_config(), _traffic()
    model = reference.config_module(cfg)
    dims = model.layer_dims(cfg)
    n_params = sum(i * o + o for i, o in zip(dims[:-1], dims[1:]))
    assert n_params == 855_050
    blocks = reference.blocks_for(
        reference.F32 * model.logits_per_example(cfg),
        int(tr["fleet"]["k"]), int(cfg["b_max"]), n_params,
        int(cfg["n_test"]))
    assert blocks == reference.Blocks(
        in_turn=False, client_rows=128, loss_rows=6 * 128,
        test_rows=10_000)


def test_block_rows_divide_evenly():
    assert reference.block_rows(10, 4, 100) == 10
    assert reference.block_rows(10, 4, 39) == 5
    assert reference.block_rows(12, 4, 35) == 6
    assert reference.block_rows(7, 4, 20) == 1
    assert reference.block_rows(8, 400, 1) == 1


# ---------------------------------------------------------------------------
# spec: keywords passed to ScenarioSpec
# ---------------------------------------------------------------------------


def test_spec_passes_through():
    cfg = _mlp_config(spec={"local_steps": 2, "scheme": "feel"})
    specs = workload.make_specs(cfg, _traffic(), 4)
    assert {(s.local_steps, s.scheme) for s in specs} == {(2, "feel")}
    assert workload._spec_value([1, [2, {"a": [3]}]]) == (1, (2, {"a": (3,)}))


def test_hidden_and_depth_pass_only_where_given():
    cfg = _mlp_config()
    del cfg["hidden"], cfg["depth"]
    from repro.api import ScenarioSpec
    spec = workload.make_specs(cfg, _traffic(), 4)[0]
    assert (spec.hidden, spec.depth) == (ScenarioSpec.hidden,
                                         ScenarioSpec.depth)


@pytest.mark.parametrize("spec,named", [
    ({"policy": "full"}, "policy"),          # set from the traffic
    ({"hidden": 64}, "hidden"),              # set from the configuration
    ({"no_such_field": 1}, "no_such_field"),  # ScenarioSpec has no such key
    ({"replan": 0}, "replan"),               # ScenarioSpec rejects its value
])
def test_spec_key_that_cannot_be_used(spec, named):
    with pytest.raises(workload.CellError, match=named):
        workload.make_specs(_mlp_config(spec=spec), _traffic(), 4)


def test_unusable_spec_exits_2_without_a_result(tiny_root, monkeypatch,
                                                capsys):
    """The whole run on the CPU, with the harness's look for a chip
    skipped: a spec key the harness sets is a cell that cannot be used."""
    import counts
    import run

    root = tiny_root("feel_mlp.fig45_gpu6",
                     cfg_over={"spec": {"policy": "full"}})
    monkeypatch.setattr(run, "ROOT", root)
    monkeypatch.setattr(run, "require_chips",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(counts, "peaks", lambda kind: {})
    with pytest.raises(SystemExit) as exit_:
        run.main(["--workload", "feel_mlp.fig45_gpu6", "--seed", "5",
                  "--seconds", "0.1"])
    assert exit_.value.code == 2
    assert capsys.readouterr().out == ""


# ---------------------------------------------------------------------------
# data: token text from the seed
# ---------------------------------------------------------------------------

TOKENS = {"vocab": 64, "seq_len": 48, "n_train": 600, "n_test": 200,
          "data": {"kind": "markov_tokens", "topics": 3, "branch": 4,
                   "zipf": 1.1}}


def test_markov_tokens_are_the_seeds():
    a_tr, a_te = workload.make_data(TOKENS, 2**40 + 3)
    b_tr, b_te = workload.make_data(TOKENS, 2**40 + 3)
    c_tr, _ = workload.make_data(TOKENS, 2**40 + 4)
    for got, want in ((a_tr.x, b_tr.x), (a_tr.y, b_tr.y), (a_te.x, b_te.x)):
        np.testing.assert_array_equal(got, want)
    assert not np.array_equal(a_tr.x, c_tr.x)
    assert a_tr.x.shape == (600, 49) and a_te.x.shape == (200, 49)
    assert a_tr.x.dtype == np.int32 and a_tr.y.dtype == np.int32
    x = np.concatenate([a_tr.x, a_te.x])
    y = np.concatenate([a_tr.y, a_te.y])
    assert x.min() >= 0 and x.max() < 64
    assert set(np.unique(y)) == {0, 1, 2}


@pytest.mark.parametrize("missing", ["topics", "branch", "zipf"])
def test_markov_tokens_need_every_key(missing):
    cfg = dict(TOKENS, data={k: v for k, v in TOKENS["data"].items()
                             if k != missing})
    with pytest.raises(workload.CellError, match=missing):
        workload.make_data(cfg, 1)


def test_unknown_data_kind_is_refused():
    with pytest.raises(workload.CellError, match="no_such_kind"):
        workload.make_data(dict(TOKENS, data={"kind": "no_such_kind"}), 1)


def _entropy_bits(counts):
    p = counts[counts > 0] / counts.sum()
    return float(-(p * np.log2(p)).sum())


def test_markov_next_token_entropy():
    """Per topic, the next token's entropy given the current one matches
    the entropy of successor j ∝ 1/(j+1) over the ``branch`` successors.
    With a vocabulary of 4,096 and Zipf exponent 0.5, two successors of
    one (topic, token) share an id in about 0.3% of contexts, which lowers
    the mean by about 0.002 bits.  Read over the contexts seen 100 times
    or more, with the Miller-Madow correction (observed successors − 1)/
    (2·n·ln 2) for the plug-in estimator's downward bias; the tolerance,
    0.05 bits, is several times the sampling noise of that mean."""
    cfg = {"vocab": 4096, "seq_len": 128, "n_train": 2000, "n_test": 10,
           "data": {"kind": "markov_tokens", "topics": 2, "branch": 4,
                    "zipf": 0.5}}
    train, _ = workload.make_data(cfg, 77)
    want = _entropy_bits(1.0 / np.arange(1, 5))
    for topic in range(2):
        x = train.x[train.y == topic]
        pairs, counts = np.unique(
            np.stack([x[:, :-1].ravel(), x[:, 1:].ravel()], 1), axis=0,
            return_counts=True)
        got, seen = [], 0
        for c in np.unique(pairs[:, 0]):
            n_c = counts[pairs[:, 0] == c]
            if n_c.sum() >= 100:
                got.append((_entropy_bits(n_c) + (len(n_c) - 1)
                            / (2 * n_c.sum() * math.log(2))) * n_c.sum())
                seen += n_c.sum()
        assert seen > 20_000
        assert abs(sum(got) / seen - want) < 0.05, (topic, want)


# ---------------------------------------------------------------------------
# the reference in blocks agrees with the single pass
# ---------------------------------------------------------------------------

MLP = {"model_family": "feel_mlp", "input_dim": 12, "hidden": 8, "depth": 3,
       "classes": 5, "n_train": 24, "n_test": 12, "spread": 6.0}


def _row_plan(n_train, periods=2, k=3, cohort=2, slot=4, seed=0):
    """``periods`` periods of ``k`` clients, ``cohort`` of them active
    each period, ``slot`` examples a client, B_k between 1 and ``slot``."""
    rng = np.random.default_rng(seed)
    active = np.zeros((periods, k), np.float32)
    for p in range(periods):
        active[p, rng.permutation(k)[:cohort]] = 1
    batch = np.where(active > 0, rng.integers(1, slot + 1, (periods, k)), 0)
    weight = (np.arange(slot) < batch[..., None]).astype(np.float32)
    return {"cohort": jnp.asarray(reference.cohorts(active)),
            "idx": jnp.asarray(rng.integers(0, n_train, (periods, k, slot)),
                               jnp.int32),
            "weight": jnp.asarray(weight),
            "batch": jnp.asarray(batch, jnp.float32),
            "lr": jnp.asarray([0.5, 0.3][:periods], jnp.float32),
            "aggden": jnp.zeros((periods,), jnp.float32)}


@pytest.mark.parametrize("cfg", [MLP, TOY_LM], ids=["mlp", "mamba2"])
@pytest.mark.parametrize("compress", [True, False], ids=["sbc", "dense"])
def test_blocked_reference_agrees_with_the_single_pass(cfg, compress):
    """Forced to block at a tiny size, the reference agrees with its
    single pass within 1e-5 relative: blocking only reorders float32 sums
    (of a few terms here, each off by an ulp or so, 6e-8 relative),
    carried through two SGD periods; 1e-5 leaves two orders of magnitude.
    The SBC ratio keeps 30% of each leaf; no kept set of these seeds sits
    on a tie that round-off could flip."""
    model = reference.config_module(cfg)
    train, test = workload.make_data(cfg, 3)
    train_in = model.train_inputs(cfg, train)
    test_in = model.test_inputs(cfg, test)
    params0 = model.init(cfg, 8)
    plan = _row_plan(cfg["n_train"])
    n_params = sum(p.size for p in jax.tree_util.tree_leaves(params0))
    ex = reference.F32 * model.logits_per_example(cfg)
    whole = reference.make_trajectory(model, cfg, 0.3, compress,
                                      jnp.float32)
    want = jax.device_get(whole(params0, plan, train_in, test_in))
    # one example a block, and two, with the cohort in turn
    for block_bytes in (1, 2 * ex):
        b = reference.blocks_for(ex, 2, 4, n_params, 12, block_bytes)
        assert b.in_turn and b.client_rows < 4 and b.test_rows < 12
        run = reference.make_trajectory(model, cfg, 0.3, compress,
                                        jnp.float32, block_bytes)
        got = jax.device_get(run(params0, plan, train_in, test_in))
        np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
        np.testing.assert_allclose(got[1], want[1], rtol=1e-5)
        for part in (2, 3):
            for g, w in zip(jax.tree_util.tree_leaves(got[part]),
                            jax.tree_util.tree_leaves(want[part])):
                np.testing.assert_allclose(
                    g, w, rtol=1e-5, atol=1e-5 * float(np.abs(w).max()))


def _plain_recurrence(A, xs, Bm, Cm, dt, prec):
    """The token-by-token scan with no blocks and no checkpoint."""
    rep = xs.shape[2] // Bm.shape[2]
    Bm, Cm = jnp.repeat(Bm, rep, 2), jnp.repeat(Cm, rep, 2)

    def step(state, t):
        xt, bt, ct, dtt = t
        state = (jnp.exp(dtt * A)[..., None, None] * state
                 + (dtt[..., None] * xt)[..., None] * bt[:, :, None, :])
        return state, jnp.einsum("bhpn,bhn->bhp", state, ct, precision=prec)

    b, _, h, p = xs.shape
    state0 = jnp.zeros((b, h, p, Bm.shape[-1]), xs.dtype)
    seq = tuple(jnp.moveaxis(a, 1, 0) for a in (xs, Bm, Cm, dt))
    return jnp.moveaxis(jax.lax.scan(step, state0, seq)[1], 0, 1)


@pytest.mark.parametrize("S", [9, 10])
def test_checkpointed_recurrence_matches_the_plain_scan(S):
    """Values and gradients of the blocked, checkpointed recurrence equal
    the plain scan's to float32 round-off (S = 9: three blocks of 3;
    S = 10: three blocks of 4, the last one padded)."""
    mamba = reference.model_module("mamba2")
    ks = jax.random.split(jax.random.key(0), 6)
    b, H, P, G, N = 2, 4, 3, 2, 5
    args = (-jnp.exp(jax.random.normal(ks[0], (H,))),
            jax.random.normal(ks[1], (b, S, H, P)),
            jax.random.normal(ks[2], (b, S, G, N)),
            jax.random.normal(ks[3], (b, S, G, N)),
            jax.nn.softplus(jax.random.normal(ks[4], (b, S, H))))
    r = jax.random.normal(ks[5], (b, S, H, P))

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a) * r)

    blocked = partial(mamba.recurrence, prec=HIGHEST)
    plain = partial(_plain_recurrence, prec=HIGHEST)
    np.testing.assert_allclose(blocked(*args), plain(*args), rtol=1e-6,
                               atol=1e-6)
    g_b = jax.grad(loss(blocked), argnums=range(5))(*args)
    g_p = jax.grad(loss(plain), argnums=range(5))(*args)
    for got, want in zip(g_b, g_p):
        np.testing.assert_allclose(got, want, rtol=1e-5,
                                   atol=1e-6 * float(np.abs(want).max()))


def test_mamba2_reference_needs_token_text():
    cfg = dict(TOY_LM, data={"kind": "clusters"})
    with pytest.raises(ValueError, match="markov_tokens"):
        reference.config_module(cfg).train_inputs(cfg, None)
