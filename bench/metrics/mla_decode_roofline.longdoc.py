"""Latent decode kernel: least time for its live rows over its device time (%)."""
from harness import costs
from harness.readings import _traced_calls


def read(ctx):
    t = ctx.trace.kernel_s.get("mla_decode", 0.0) if ctx.trace else 0.0
    if not _traced_calls(ctx) or t <= 0:
        return None
    layers = ctx.model["num_hidden_layers"]
    ideal = sum(layers * costs.min_time_s(
        *costs.paged_decode_cost(c.ctx, ctx.model), ctx.peak)
        for c in ctx.calls if c.kind == "decode")
    return 100.0 * ideal / t
