"""Share of the traced window in which no operation ran on the chip, mean over chips (%)."""
from harness.readings import device_idle_share as read
