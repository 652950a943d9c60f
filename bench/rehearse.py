"""Compile a cell's bucket program for a described TPU v5e chip, with no
chip attached, and print what the compiler says it needs.

    JAX_PLATFORMS=cpu python bench/rehearse.py <cell> [--rows N]

Plans the cell's bucket on the host at its full size (``--rows`` replaces
the traffic's seeds per spec, to ask whether N rows fit one chip), lowers
the program the dispatch phase would run (``lowering.trace_bucket``) and
compiles it for one chip of a ``v5e:2x2`` topology.  The program picks
its Pallas kernels by asking JAX for the default backend, which here is
the CPU; the rehearsal answers "tpu" for it, so the program compiled is
the one the chip runs.  Prints one JSON line
with the program's argument, output and temporary bytes.  No times: a
compile here says nothing about speed.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import workload  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("cell")
    ap.add_argument("--rows", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    from jax.experimental import topologies
    from jax.extend.core import jaxpr_as_fun
    from jax.sharding import SingleDeviceSharding
    from repro.api import Experiment
    from repro.api import lowering
    from repro.kernels import ops

    ops._on_tpu = lambda: True

    cell = workload.find_cell(ROOT, args.cell)
    tr = dict(cell.traffic)
    if args.rows is not None:
        tr["seeds_per_spec"] = args.rows
    train, test = workload.make_data(cell.config, args.seed)
    specs = workload.make_specs(cell.config, tr, args.seed)
    (bucket,) = Experiment(train, test, specs).lower()
    plan = lowering.plan_bucket(bucket, train, int(tr["periods"]))
    traced = lowering.trace_bucket(plan, train, test)

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    avals = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one)
             for a in traced.closed.in_avals]
    compiled = jax.jit(jaxpr_as_fun(traced.closed)).lower(*avals).compile()
    mem = compiled.memory_analysis()
    out = {"cell": args.cell, "rows": len(bucket.rows),
           "k_pad": bucket.k_pad, "periods": int(tr["periods"]),
           "kernels": compiled.as_text().count(
               'custom_call_target="tpu_custom_call"')}
    for name in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "generated_code_size_in_bytes"):
        out[name] = int(getattr(mem, name, -1))
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
