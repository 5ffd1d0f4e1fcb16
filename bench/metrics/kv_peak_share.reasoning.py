"""Largest share of the KV page pool in use after any step of the window (%)."""
from harness.readings import kv_peak_share as read
