"""Active lanes per decode model call in the window."""
from harness.readings import decode_lanes_mean as read
