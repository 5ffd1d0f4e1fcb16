"""Paged decode kernel: least time for its live context over its device time (%)."""
from harness.readings import paged_decode_roofline as read
