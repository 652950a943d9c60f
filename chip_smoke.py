"""Smoke run of the FEEL training path on the TPU.

    python chip_smoke.py             # one chip: feel_mlp, transformer, mamba2
    python chip_smoke.py --chips 4   # only the four-chip MeshExecutor phase

Every phase drives the user entry point, ``Experiment(...).run(...)``:

* ``feel_mlp`` — the paper's model at ``configs/feel_mlp.py`` widths
  (3072 → 256 → 256 → 10) on the table-2 K=6 fleet, 4 seeds.  Every series
  is finite, loss falls, and seed 0 agrees with the seed-semantics loop
  ``FeelSimulation(engine="python")`` run on the same chip.
* ``transformer`` / ``mamba2`` at the spec defaults (hidden 256, depth 3,
  SBC on).  Series are finite, the compiled bucket program holds a
  ``tpu_custom_call`` (the Pallas kernels ran, not the jnp references),
  and the family's kernel agrees with its reference at the family shapes,
  forward and gradient.
* ``sbc`` — the per-client SBC kernels (vmapped over K) against
  ``sbc_tensor(exact=True)``.
* ``mesh`` (``--chips 4`` only) — the feel_mlp grid sharded over four
  chips by ``MeshExecutor`` against ``SerialExecutor`` on one.

Each phase prints one JSON line: shapes, compile and run seconds (smoke
timings, not benchmark metrics), errors against its reference.  The last
line, ``{"ok": true, "device": {...}}``, is printed only when every phase
passed; otherwise the exit code is 1.  Without a TPU, or outside a
checkout of the repo, it exits 2 before any work.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"

PERIODS = 20          # feel_mlp horizon
FAMILY_PERIODS = 3    # big-model horizon
SEEDS = (0, 1, 2, 3)
# the CPU tests' tolerances for the same comparisons: scan engine vs the
# python loop (tests/test_engine.py), sharded vs plain (tests/test_api.py)
ENGINE_TOL = dict(atol=1e-5, rtol=1e-5)
MESH_TOL = dict(atol=1e-5)
# kernel vs its jnp reference, as a share of the reference's largest
# magnitude (the reference runs at full f32 matmul precision)
KERNEL_REL_TOL = 1e-2
SBC_REL_TOL = 1e-5


def fail_early(msg: str):
    print(f"chip_smoke: {msg}", file=sys.stderr)
    raise SystemExit(2)


def require_tpu(chips: int):
    if not (SRC / "repro").is_dir():
        fail_early(f"no repro package under {SRC}; run from a checkout")
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail_early(f"needs a TPU, but JAX found platform "
                   f"{devices[0].platform!r} ({len(devices)} device(s))")
    if len(devices) < chips:
        fail_early(f"--chips {chips} needs {chips} TPU chips, "
                   f"JAX found {len(devices)}")
    return devices


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def rel_err(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                  1e-30))


def peak_bytes() -> int:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


# ---------------------------------------------------------------------------
# the workload: configs/feel_mlp.py widths on the table-2 fleet
# ---------------------------------------------------------------------------


def dataset():
    from repro.configs.feel_mlp import INPUT_DIM
    from repro.data.pipeline import ClassificationData
    full = ClassificationData.synthetic(n=12000, dim=INPUT_DIM, seed=0,
                                        spread=6.0)
    return full.split(1200)


def spec(**kw):
    from benchmarks.table2_schemes import fleet
    from repro.api import ScenarioSpec
    from repro.configs.feel_mlp import CONFIG
    base = dict(fleet=fleet(6), name="K6", scheme="feel",
                partition="noniid", policy="proposed", compress=True,
                b_max=128, base_lr=0.05, seeds=SEEDS,
                hidden=CONFIG.d_model, depth=CONFIG.n_layers)
    base.update(kw)
    return ScenarioSpec(**base)


def run_twice(exp, periods, **kw):
    """First call (trace + compile + run) and a warm call (run only)."""
    res, first_s = timed(lambda: exp.run(periods, **kw))
    again, warm_s = timed(lambda: exp.run(periods, **kw))
    return res, again, dict(compile_s=first_s - warm_s, run_s=warm_s)


def loss_falls(losses, window: int = 5) -> bool:
    """Each period's loss is over that period's non-IID batch and jumps
    with it, so compare the mean over every seed of the first and the
    last ``window`` periods."""
    return bool(np.mean(losses[:, -window:]) < np.mean(losses[:, :window]))


def finite(res) -> bool:
    return all(np.isfinite(a).all() for a in (res.losses, res.accs,
                                              res.times))


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def phase_feel_mlp(data, test):
    from repro.api import Experiment
    from repro.fed.trainer import FeelSimulation
    s = spec()
    res, again, t = run_twice(Experiment(data, test, [s]), PERIODS)
    sim = FeelSimulation(list(s.fleet), data, test, partition=s.partition,
                         policy=s.policy, compress=s.compress, b_max=s.b_max,
                         base_lr=s.base_lr, seed=0, hidden=s.hidden,
                         depth=s.depth, engine="python")
    ref, ref_s = timed(lambda: sim.run(PERIODS, eval_every=1))
    row = res.sel(seed=0)
    checks = {
        "finite": finite(res),
        "loss_falls": loss_falls(res.losses),
        "times_equal_ref": bool(np.array_equal(row.times[0], ref.times)),
        "losses_match_ref": bool(np.allclose(row.losses[0], ref.losses,
                                             **ENGINE_TOL)),
        "accs_match_ref": bool(np.allclose(row.accs[0], ref.accs,
                                           **ENGINE_TOL)),
    }
    return checks, dict(
        rows=res.rows, periods=PERIODS, data=list(data.x.shape),
        test=list(test.x.shape), hidden=s.hidden, depth=s.depth, k=s.k,
        b_max=s.b_max, **t, ref_run_s=ref_s,
        loss_first=res.losses[:, 0].tolist(),
        loss_last=res.losses[:, -1].tolist(),
        acc_last=res.accs[:, -1].tolist(),
        max_abs_loss_vs_ref=float(np.max(np.abs(row.losses[0]
                                                - ref.losses))),
        max_abs_acc_vs_ref=float(np.max(np.abs(row.accs[0] - ref.accs))),
        rerun_identical=bool(np.array_equal(res.losses, again.losses)),
        peak_bytes=peak_bytes())


def bucket_kernel_count(exp, data, test, periods):
    """Compile the bucket's program from the jaxpr the dispatch runs
    (``lowering.trace_bucket``) and count its Pallas kernels."""
    import jax
    from jax.extend.core import jaxpr_as_fun
    from repro.api import lowering
    (bucket,) = exp.lower()
    traced = lowering.trace_bucket(lowering.plan_bucket(bucket, data,
                                                        periods), data, test)
    avals = [jax.ShapeDtypeStruct(a.shape, a.dtype)
             for a in traced.closed.in_avals]
    compiled, aot_s = timed(
        lambda: jax.jit(jaxpr_as_fun(traced.closed)).lower(*avals).compile())
    n = compiled.as_text().count('custom_call_target="tpu_custom_call"')
    mem = compiled.memory_analysis()
    return n, dict(bucket_compile_s=aot_s,
                   bucket_temp_bytes=int(getattr(mem, "temp_size_in_bytes",
                                                 -1)))


def fwd_and_vjp(fn):
    """jit(primal, cotangent) -> (out, grads of every primal)."""
    import jax

    def run(*args):
        *primals, ct = args
        out, vjp = jax.vjp(fn, *primals)
        return out, vjp(ct)

    return jax.jit(run)


def parity(kern, oracle, args, ct):
    """Kernel vs reference, forward and gradients; the reference runs at
    full f32 matmul precision."""
    import jax
    (out_k, g_k), kern_s = timed(
        lambda: jax.block_until_ready(fwd_and_vjp(kern)(*args, ct)))
    with jax.default_matmul_precision("highest"):
        out_r, g_r = fwd_and_vjp(oracle)(*args, ct)
    errs = {"fwd": rel_err(out_k, out_r)}
    for i, (a, b) in enumerate(zip(g_k, g_r)):
        errs[f"grad{i}"] = rel_err(a, b)
    return errs, kern_s


def phase_family(family, data, test):
    from repro.api import Experiment
    exp = Experiment(data, test, [spec(model_family=family, seeds=(0,))])
    res, _, t = run_twice(exp, FAMILY_PERIODS)
    n_kernels, bucket = bucket_kernel_count(exp, data, test, FAMILY_PERIODS)
    errs, shapes, kern_s = (family_parity_flash() if family == "transformer"
                            else family_parity_ssd())
    checks = {"finite": finite(res), "bucket_has_kernels": n_kernels > 0,
              "kernel_parity": max(errs.values()) <= KERNEL_REL_TOL}
    return checks, dict(
        rows=res.rows, periods=FAMILY_PERIODS, **t, **bucket,
        bucket_kernels=n_kernels, loss_first=res.losses[:, 0].tolist(),
        loss_last=res.losses[:, -1].tolist(), parity_shapes=shapes,
        parity_rel_err=errs, parity_compile_run_s=kern_s,
        peak_bytes=peak_bytes())


def _normal(i, shape, scale=1.0):
    import jax
    return jax.random.normal(jax.random.fold_in(jax.random.key(0), i),
                             shape) * scale


def family_parity_flash():
    """``ops.flash_attention`` vs the model's ``attn_impl="naive"`` path at
    the transformer family's shapes (BH = 128·4 = 512, S = 16, hd = 64)."""
    import jax.numpy as jnp
    from repro.fed.model_engine import SEQ_CAP, family_arch
    from repro.kernels import ops
    from repro.models.attention import attend
    cfg = family_arch("transformer", 256, 3)
    hd = cfg.d_model // cfg.n_heads
    q = _normal(0, (128, SEQ_CAP, cfg.n_heads, hd))
    k = _normal(1, (128, SEQ_CAP, cfg.n_kv_heads, hd))
    v = _normal(2, (128, SEQ_CAP, cfg.n_kv_heads, hd))
    ct = _normal(3, q.shape)
    pos = jnp.arange(SEQ_CAP)
    errs, s = parity(
        lambda q, k, v: ops.flash_attention(q, k, v),
        lambda q, k, v: attend(q, k, v, pos, pos, impl="naive"),
        (q, k, v), ct)
    return errs, [list(q.shape), list(k.shape)], s


def family_parity_ssd():
    """``ops.ssd`` vs ``ssd_reference`` at the mamba2 family's shapes
    (64 heads of 8, one 16-state group, S = 16, the family chunk)."""
    import jax
    import jax.numpy as jnp
    from repro.fed.model_engine import SEQ_CAP, family_arch
    from repro.kernels import ops
    from repro.models.mamba2 import dims, ssd_reference
    cfg = family_arch("mamba2", 256, 3)
    s = cfg.ssm
    _, H, _ = dims(cfg)
    B = 128
    x = _normal(0, (B, SEQ_CAP, H, s.head_dim))
    dt = jax.nn.softplus(_normal(1, (B, SEQ_CAP, H)))
    A = -jnp.linspace(1.0, 16.0, H)                   # mamba2_init's range
    Bm = _normal(2, (B, SEQ_CAP, s.n_groups, s.d_state), 0.5)
    Cm = _normal(3, (B, SEQ_CAP, s.n_groups, s.d_state), 0.5)
    ct = _normal(4, x.shape)
    errs, t = parity(
        lambda *a: ops.ssd(*a, chunk=s.chunk),
        lambda *a: ssd_reference(*a, s.chunk)[0],
        (x, dt, A, Bm, Cm), ct)
    return errs, [list(x.shape), list(Bm.shape), s.chunk], t


def phase_sbc():
    """The per-client SBC kernels vs ``sbc_tensor(exact=True)`` on a
    K=6 stack of 256×512 leaves (the transformer's MLP weight)."""
    import jax
    from repro.compression.sbc import sbc_tensor
    from repro.kernels import ops
    g = _normal(5, (6, 256, 512), 2.0)
    got, s = timed(lambda: jax.block_until_ready(
        jax.jit(jax.vmap(lambda t: ops.sbc_compress(t, 0.005)))(g)))
    want = jax.jit(jax.vmap(lambda t: sbc_tensor(t, 0.005, exact=True)))(g)
    got, want = np.asarray(got), np.asarray(want)
    err = rel_err(got, want)
    checks = {"support_equal": bool(np.array_equal(got != 0, want != 0)),
              "values_match": err <= SBC_REL_TOL}
    return checks, dict(shape=list(g.shape), rel_err=err,
                        compile_run_s=s, kept=int((got != 0).sum()))


def phase_mesh(data, test):
    from repro.api import Experiment, MeshExecutor, SerialExecutor
    from repro.launch.mesh import make_batch_mesh
    exp = Experiment(data, test, [spec(partition=p)
                                  for p in ("iid", "noniid")])
    serial, _, ts = run_twice(exp, PERIODS, executor=SerialExecutor())
    mesh = make_batch_mesh(4)
    sharded, _, tm = run_twice(exp, PERIODS, executor=MeshExecutor(mesh))
    checks = {
        "finite": finite(sharded),
        "times_equal": bool(np.array_equal(serial.times, sharded.times)),
        "losses_match": bool(np.allclose(serial.losses, sharded.losses,
                                         **MESH_TOL)),
        "accs_match": bool(np.allclose(serial.accs, sharded.accs,
                                       **MESH_TOL)),
    }
    return checks, dict(
        rows=sharded.rows, periods=PERIODS, mesh=int(mesh.devices.size),
        serial=ts, sharded=tm,
        max_abs_loss=float(np.max(np.abs(serial.losses - sharded.losses))),
        max_abs_acc=float(np.max(np.abs(serial.accs - sharded.accs))))


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the MeshExecutor phase over 4 chips")
    args = ap.parse_args(argv)

    sys.path.insert(0, str(SRC))
    devices = require_tpu(args.chips)
    from repro.launch.compile_cache import use_compile_cache
    cache = use_compile_cache()
    (data, test), data_s = timed(dataset)
    print(json.dumps({"phase": "setup", "compile_cache": cache,
                      "devices": len(devices),
                      "kind": devices[0].device_kind, "data_s": data_s}),
          flush=True)
    if args.chips == 4:
        phases = [("mesh", lambda: phase_mesh(data, test))]
    else:
        phases = [("feel_mlp", lambda: phase_feel_mlp(data, test)),
                  ("transformer", lambda: phase_family("transformer", data,
                                                       test)),
                  ("mamba2", lambda: phase_family("mamba2", data, test)),
                  ("sbc", phase_sbc)]
    failed = []
    for name, run in phases:
        try:
            (checks, info), wall = timed(run)
        except Exception:                                  # noqa: BLE001
            traceback.print_exc()
            checks, info, wall = {"completed": False}, {}, float("nan")
        ok = all(checks.values())
        if not ok:
            failed.append(name)
        print(json.dumps({"phase": name, "ok": ok, "checks": checks,
                          "wall_s": wall, **info}), flush=True)
    if failed:
        print(f"chip_smoke: failed phases: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
