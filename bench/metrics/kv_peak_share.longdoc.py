"""Largest share of the page pool (latent rows here) in use after any step of the window (%)."""
from harness.readings import kv_peak_share as read
