"""Model FLOPs of the real tokens over the step programs' device time at the bf16 peak (%)."""
from harness.readings import step_mfu as read
