"""Mean device time of one prefill step program in the traced window (ms)."""
from harness.readings import call_times, mean_ms


def read(ctx):
    return mean_ms(call_times(ctx, "prefill"))
