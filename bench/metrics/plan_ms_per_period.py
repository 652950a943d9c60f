"""Host planning (``api.lowering.plan_bucket``: channel Monte-Carlo,
Algorithm 1, schedule build) in milliseconds per simulated period of a
grid call: the window's ``plan_bucket`` span seconds over the periods the
window planned."""


def read(ctx):
    periods = ctx["n_calls"] * ctx["per_call"]["periods"]
    return 1000.0 * ctx["span_s"]["plan_bucket"] / periods
