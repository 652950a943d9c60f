"""Record the small profiler trace that ``test_tracefile.py`` reads.

    python bench/tests/record_trace.py <output dir>    # on a TPU

Inside a host span ``window``: a jitted matmul chain runs three times;
between the second and third run the host sleeps 50 ms inside a span
``plan_bucket`` with nothing queued on the device, so the trace holds one
idle gap of at least that length, labelled by that span.  The
``.xplane.pb`` lands under ``<output dir>/plugins/profile/<time>/``.
"""
from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp


def main(out_dir: str) -> int:
    @jax.jit
    def chain(a):
        for _ in range(8):
            a = jnp.tanh(a @ a)
        return a

    a = jnp.ones((1024, 1024), jnp.float32) / 1024
    chain(a).block_until_ready()                       # compile outside
    jax.profiler.start_trace(out_dir)
    with jax.profiler.TraceAnnotation("window"):
        for i in range(3):
            if i == 2:
                with jax.profiler.TraceAnnotation("plan_bucket"):
                    time.sleep(0.05)
            with jax.profiler.TraceAnnotation("dispatch_bucket"):
                out = chain(a)
            with jax.profiler.TraceAnnotation("collect_bucket"):
                out.block_until_ready()
    jax.profiler.stop_trace()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
