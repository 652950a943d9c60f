"""A named architecture on token text (``ScenarioSpec.arch``): the spec
names it, ``Experiment.run`` trains it on next-token text with the clients
in turn and the logits in blocks, and it agrees with the benchmark's plain
float32 reference (``bench/models/mamba2.py`` through
``bench/reference.py``, both loaded by path).

The architecture is a small Mamba-2 (d_model 64, 2 layers, d_state 16,
head_dim 16, chunk 8, vocabulary 256) on 32-token sequences, K = 3.

Tolerance of the comparison: 1e-4 on the loss, 1/256 on the accuracy.
The program reads 1e-6 on the loss (the chunked SSD scan and the
token-by-token recurrence differ by float32 round-off; on the CPU the
program's SBC threshold is the bisection's, the reference's the exact
top-k) and an equal accuracy; the reference computed in bfloat16 reads
1e-2, a hundred times over the tolerance.
"""
import importlib.util
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import Experiment, ScenarioSpec, SerialExecutor
from repro.api import lowering
from repro.configs import ARCHS
from repro.configs.base import ArchConfig, SSMConfig
from repro.core import DeviceProfile
from repro.data.pipeline import ClassificationData
from repro.fed import model_engine
from repro.topology import Sampling

BENCH = Path(__file__).resolve().parent.parent / "bench"
tree_map = jax.tree_util.tree_map

ARCH = "mamba2-text-test"
SEQ, VOCAB, BMAX, PERIODS = 32, 256, 4, 2
LOSS_TOL, ACC_TOL = 1e-4, 1.0 / VOCAB
CFG = {"model_family": "mamba2", "reference": "mamba2", "hidden": 64,
       "depth": 2, "expand": 2, "d_state": 16, "head_dim": 16,
       "n_groups": 1, "d_conv": 4, "vocab": VOCAB, "seq_len": SEQ,
       "n_train": 96, "n_test": 8,
       "data": {"kind": "markov_tokens", "topics": 4, "branch": 4,
                "zipf": 1.1}}


def _load(name):
    spec = importlib.util.spec_from_file_location(
        f"bench_{name}_for_tests", BENCH / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


reference = _load("reference")
workload = _load("workload")
mamba_ref = reference.config_module(CFG)


@pytest.fixture(scope="module", autouse=True)
def archs():
    """The test architecture, registered as a ``repro.configs`` entry."""
    ARCHS[ARCH] = ArchConfig(
        name=ARCH, family="ssm", n_layers=2, d_model=64, n_heads=0,
        n_kv_heads=0, d_ff=0, vocab=VOCAB, attn_kind="none",
        ssm=SSMConfig(d_state=16, head_dim=16, chunk=8))
    yield
    del ARCHS[ARCH]


@pytest.fixture(scope="module")
def text():
    return workload.make_data(CFG, 11)


@pytest.fixture(scope="module")
def fleet():
    return tuple(DeviceProfile(kind="gpu", gpu_t_low=t, gpu_slope=4e-4,
                               gpu_b_th=16) for t in (0.02, 0.025, 0.03))


def _spec(fleet, **kw):
    kw.setdefault("name", "text3")
    kw.setdefault("model_family", "mamba2")
    kw.setdefault("arch", ARCH)
    kw.setdefault("hidden", 64)
    kw.setdefault("depth", 2)
    kw.setdefault("seq_len", SEQ)
    kw.setdefault("b_max", BMAX)
    kw.setdefault("compression", 0.05)
    kw.setdefault("base_lr", 1.0)
    kw.setdefault("seeds", (7,))
    return ScenarioSpec(fleet=fleet, **kw)


def _program(spec, train, test):
    """One bucket through the lowering's phases: the plan, the program's
    (losses, accs) and its final weights (row 0)."""
    (bucket,) = lowering.group_rows([spec])
    plan = lowering.plan_bucket(bucket, train, PERIODS)
    handle = lowering.dispatch_bucket(plan, train, test)
    losses, accs, _, _ = lowering.collect_bucket(handle)
    final = tree_map(lambda a: np.asarray(a[0]), handle.state.params)
    return plan, losses[0], accs[0], final


def _reference(plan, seed, train, test, compress, ratio, dtype=jnp.float32):
    arrays = workload.plan_arrays(plan)
    run = reference.make_trajectory(mamba_ref, CFG, ratio, compress, dtype)
    losses, accs, params, _ = jax.device_get(run(
        mamba_ref.init(CFG, seed), reference.row_plan(arrays, 0),
        mamba_ref.train_inputs(CFG, train), mamba_ref.test_inputs(CFG, test)))
    return losses, accs, params


# ---------------------------------------------------------------------------
# the spec names the architecture
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("field,kw", [
    ("arch", {"arch": "no-such-model"}),
    ("arch", {"arch": "granite-34b", "model_family": "transformer"}),
    ("hidden", {"hidden": 32}),
    ("depth", {"depth": 3}),
    ("depth", {"depth": 0}),
    ("seq_len", {"seq_len": 36}),
    ("seq_len", {"seq_len": None}),
    ("model_family", {"model_family": "transformer"}),
    ("model_family", {"model_family": "feel_mlp"}),
], ids=lambda v: v if isinstance(v, str) else "-".join(map(str, v.values())))
def test_spec_rejects_and_names_the_field(fleet, field, kw):
    with pytest.raises(ValueError, match=field):
        _spec(fleet, **kw)


def test_seq_len_needs_an_arch(fleet):
    with pytest.raises(ValueError, match="seq_len"):
        ScenarioSpec(fleet=fleet, seq_len=32)


def test_feature_data_with_an_arch_names_the_field(fleet):
    data = ClassificationData.synthetic(n=60, dim=12, seed=0)
    train, test = data.split(10)
    with pytest.raises(ValueError, match="arch"):
        Experiment(train, test, [_spec(fleet)]).run(PERIODS)


def test_token_ids_outside_the_vocabulary_name_the_field(fleet, text):
    train, test = text
    x = np.array(train.x)
    x[0, 3] = VOCAB
    bad = ClassificationData(x, np.asarray(train.y))
    with pytest.raises(ValueError, match="token ids"):
        Experiment(bad, test, [_spec(fleet)]).run(PERIODS)


def test_arch_and_seq_len_are_structural(fleet):
    """Specs that differ in the named architecture's text shape compile
    separately and plan separately; the spec without ``arch`` keeps its
    key."""
    a, b = _spec(fleet), _spec(fleet, seq_len=2 * SEQ)
    assert a.bucket_key() != b.bucket_key()
    assert a.bucket_key()[-1] == "mamba2"
    ra, rb = (lowering.Row(spec=s, seed=7, indices=(0,)) for s in (a, b))
    assert lowering._plan_key(ra) != lowering._plan_key(rb)
    toy = ScenarioSpec(fleet=fleet, model_family="mamba2")
    assert toy.arch is None and toy.bucket_key()[-3:-1] == (None, None)


def test_uplink_priced_at_the_cut_architecture():
    """Mamba-2 2.7B cut to 4 layers: 418,425,280 parameters with the
    untied head and the vocabulary padded to 50,304 (counted from
    shapes)."""
    fleet = (DeviceProfile(kind="gpu"),)
    spec = ScenarioSpec(fleet=fleet, model_family="mamba2",
                        arch="mamba2-2.7b", hidden=2560, depth=4,
                        seq_len=2048)
    assert lowering._n_params(spec, 2049) == 418_425_280


def test_no_pass_holds_more_than_a_gib_of_logits():
    """At the benchmark cell's sizes a logit block holds 2 sequences of
    2,048 positions over 50,304 ids: 0.82 GB."""
    block = model_engine.logit_block("mamba2", 2560, 4, "mamba2-2.7b",
                                     2048, 8)
    assert block == 2
    cfg = model_engine.model_arch("mamba2", 2560, 4, "mamba2-2.7b")
    assert block * model_engine.seq_logit_bytes(cfg, 2048) <= 1 << 30


# ---------------------------------------------------------------------------
# the program against the plain reference
# ---------------------------------------------------------------------------


def test_initial_weights_are_the_references_bitwise(fleet, text):
    """The program's weights from a row seed have the reference's leaves,
    paths and shapes (vocabulary padded to a multiple of 128) and equal
    its documented init bit for bit."""
    spec = _spec(fleet)
    (bucket,) = lowering.group_rows([spec])
    got = reference.leaves_by_path(tree_map(
        lambda a: a[0], lowering._init_params_batch(bucket.rows, SEQ + 1)))
    want = reference.leaves_by_path(mamba_ref.init(CFG, 7))
    assert sorted(got) == sorted(want)
    for path in want:
        assert got[path].shape == want[path].shape, path
        np.testing.assert_array_equal(got[path], want[path], err_msg=path)
    assert got["['embed']['table']"].shape[0] == 256


@pytest.fixture(scope="module")
def runs(fleet, text):
    """Per SBC setting: the plan, the program's series and weights, the
    float32 reference's, and the bfloat16 reference's series."""
    train, test = text
    out = {}
    for compress in (True, False):
        spec = _spec(fleet, compress=compress)
        plan, losses, accs, final = _program(spec, train, test)
        ref = _reference(plan, 7, train, test, compress, 0.05)
        bf16 = _reference(plan, 7, train, test, compress, 0.05,
                          jnp.bfloat16)
        out[compress] = (plan, (losses, accs, final), ref, bf16)
    return out


@pytest.mark.parametrize("compress", [True, False], ids=["sbc", "dense"])
def test_program_agrees_with_the_reference(runs, compress):
    _, (losses, accs, final), (l_ref, a_ref, p_ref), _ = runs[compress]
    assert np.all(np.isfinite(losses)) and losses[1] < losses[0]
    np.testing.assert_allclose(losses, l_ref, rtol=0, atol=LOSS_TOL)
    np.testing.assert_allclose(accs, a_ref, rtol=0, atol=ACC_TOL)
    got, want = (reference.leaves_by_path(p) for p in (final, p_ref))
    for path in want:
        np.testing.assert_allclose(got[path], want[path], rtol=1e-4,
                                   atol=1e-4 * np.abs(want[path]).max(),
                                   err_msg=path)


@pytest.mark.parametrize("compress", [True, False], ids=["sbc", "dense"])
def test_bfloat16_reference_fails_the_tolerance(runs, compress):
    _, _, (l_ref, _, _), (l_bf, _, _) = runs[compress]
    assert np.max(np.abs(np.asarray(l_bf, np.float64) - l_ref)) > LOSS_TOL


def test_blocked_logits_match_the_single_pass(runs, fleet, text,
                                              monkeypatch):
    """Blocks of one sequence's logits (a client's gradient in 4 blocks,
    the loss and test passes in 12 and 8) give the single pass's series
    and weights to float32 round-off."""
    train, test = text
    _, (losses, accs, final), _, _ = runs[True]
    monkeypatch.setattr(model_engine, "LOGIT_BLOCK_BYTES", 4 * SEQ * VOCAB)
    assert model_engine.logit_block("mamba2", 64, 2, ARCH, SEQ, BMAX) == 1
    _, b_losses, b_accs, b_final = _program(_spec(fleet), train, test)
    np.testing.assert_allclose(b_losses, losses, rtol=1e-6)
    np.testing.assert_array_equal(b_accs, accs)
    for g, w in zip(jax.tree_util.tree_leaves(b_final),
                    jax.tree_util.tree_leaves(final)):
        np.testing.assert_allclose(g, w, rtol=1e-5,
                                   atol=1e-6 * np.abs(w).max())


def test_experiment_run_gives_the_lowering_series(runs, fleet, text):
    """``Experiment.run`` with ``SerialExecutor`` runs the same program."""
    train, test = text
    _, (losses, accs, _), _, _ = runs[True]
    res = Experiment(train, test, [_spec(fleet)]).run(
        PERIODS, executor=SerialExecutor())
    np.testing.assert_array_equal(np.asarray(res.losses)[0], losses)
    np.testing.assert_array_equal(np.asarray(res.accs)[0], accs)


# ---------------------------------------------------------------------------
# a client outside the cohort keeps its residual
# ---------------------------------------------------------------------------


def test_sampled_out_client_agrees_with_the_reference(fleet, text):
    """Two of three clients a period (``Sampling(size=2)``) over 6
    periods, in which a client takes part, sits out and takes part
    again: the reference keeps an absent client's residual, and so does
    the program (a program that drains it reads 0.12 here)."""
    train, test = text
    spec = _spec(fleet, sampling=Sampling(size=2), seeds=(9,))
    (bucket,) = lowering.group_rows([spec])
    plan = lowering.plan_bucket(bucket, train, 6)
    active = np.asarray(workload.plan_arrays(plan)["active"])[0]
    assert (active.sum(1) == 2).all()
    assert any(re.search("10+1", "".join(str(int(a)) for a in col))
               for col in active.T)
    losses, _, _, _ = lowering.collect_bucket(
        lowering.dispatch_bucket(plan, train, test))
    run = reference.make_trajectory(mamba_ref, CFG, 0.05, True, jnp.float32)
    l_ref = jax.device_get(run(
        mamba_ref.init(CFG, 9),
        reference.row_plan(workload.plan_arrays(plan), 0),
        mamba_ref.train_inputs(CFG, train),
        mamba_ref.test_inputs(CFG, test)))[0]
    np.testing.assert_allclose(losses[0], l_ref, rtol=0, atol=LOSS_TOL)


def test_inactive_or_empty_client_keeps_its_residual(fleet, text):
    """Client 2 is never in the cohort and client 1 has B_k = 0 in every
    period: both residuals come back bit for bit; client 0's moves."""
    train, test = text
    spec = _spec(fleet)
    (bucket,) = lowering.group_rows([spec])
    plan = lowering.plan_bucket(bucket, train, PERIODS)
    (sched,) = plan.payload["schedules"]
    batch = np.asarray(sched.batch).copy()
    weight = np.asarray(sched.weight).copy()
    batch[:, 1] = 0
    weight[:, 1] = 0
    sched = type(sched)(**{**sched.__dict__, "batch": batch,
                           "weight": weight})
    active = np.ones((1, 3), np.float32)
    active[0, 2] = 0
    params0 = lowering._init_params_batch(bucket.rows, SEQ + 1)
    rng = np.random.default_rng(0)
    res0 = tree_map(lambda p: rng.normal(
        size=(1, 3) + p.shape[1:]).astype(np.float32), params0)
    _, residual, _ = model_engine.run_model_trajectory_batch(
        params0, tree_map(jnp.asarray, res0), [sched], train, test,
        model_family="mamba2", hidden=64, depth=2, compress=True,
        ratio=0.05, active=active, arch=ARCH, seq_len=SEQ)
    for got, want in zip(jax.tree_util.tree_leaves(residual),
                         jax.tree_util.tree_leaves(res0)):
        got = np.asarray(got)
        np.testing.assert_array_equal(got[:, 1:], want[:, 1:])
    assert any(not np.array_equal(np.asarray(g)[:, 0], w[:, 0])
               for g, w in zip(jax.tree_util.tree_leaves(residual),
                               jax.tree_util.tree_leaves(res0)))
