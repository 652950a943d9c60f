"""Benchmark harness — one module per paper table/figure (DESIGN.md §8).
Prints ``name,us_per_call,derived`` CSV."""
from __future__ import annotations

import sys
import time


def main() -> None:
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    from benchmarks import (ablation_compression, fig2_gpu_training_function,
                            fig3_generalization, fig45_batchsize_policies,
                            fig_dynamics, fig_models, fig_replan, fig_users,
                            loss_decay_fit, roofline, serve_load,
                            smoke_experiment, solver_scaling, sweep_speed,
                            table2_schemes)
    modules = [
        ("fig2_gpu_training_function", fig2_gpu_training_function),
        ("solver_scaling", solver_scaling),
        ("loss_decay_fit", loss_decay_fit),
        ("smoke_experiment", smoke_experiment),
        ("table2_schemes", table2_schemes),
        ("fig3_generalization", fig3_generalization),
        ("fig45_batchsize_policies", fig45_batchsize_policies),
        ("ablation_compression", ablation_compression),
        ("fig_users", fig_users),
        ("fig_replan", fig_replan),
        ("fig_dynamics", fig_dynamics),
        ("fig_models", fig_models),
        ("sweep_speed", sweep_speed),
        ("roofline", roofline),
        ("serve_load", serve_load),
    ]
    print("name,us_per_call,derived")
    failures = 0
    for name, mod in modules:
        t0 = time.time()
        try:
            rows = mod.main(fast=True)
            for r in rows:
                print(",".join(str(x) for x in r), flush=True)
            print(f"_module/{name},{(time.time()-t0)*1e6:.0f},ok",
                  flush=True)
        except Exception as e:                               # noqa: BLE001
            failures += 1
            print(f"_module/{name},0,FAIL:{type(e).__name__}:{e}",
                  flush=True)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
