"""Every output token emitted in the window over the window's seconds."""
from harness.readings import output_tokens_per_s


def read(ctx):
    return output_tokens_per_s(ctx)
