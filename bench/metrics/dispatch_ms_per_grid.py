"""Dispatch and host-to-device (``api.lowering.dispatch_bucket``: initial
weights, schedule and dataset upload, program enqueue) in milliseconds
per grid call: the window's ``dispatch_bucket`` span seconds over its
grid calls."""


def read(ctx):
    return 1000.0 * ctx["span_s"]["dispatch_bucket"] / ctx["n_calls"]
