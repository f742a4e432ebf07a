"""`agg_fast_verify_msm_idx[64x4]`'s share of its roofline (benchmark/
work.py `roofline_pct`), from the one call traced after the window."""
from benchmark import work

LAYER, UNIT = "kernel", "%"
read = work.roofline_pct
