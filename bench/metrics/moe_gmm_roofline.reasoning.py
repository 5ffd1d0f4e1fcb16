"""Ragged grouped matmul: least time for its routed rows and touched experts over its device time (%)."""
from harness.readings import moe_gmm_roofline as read
