"""Run by hand, not part of tier-1:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CELLS = ["store_sales_sf10.upsert_1m",
         "store_sales_sf10.scan_date_window"]
