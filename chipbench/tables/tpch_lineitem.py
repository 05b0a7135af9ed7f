"""TPC-H ``lineitem``: all 16 columns (TPC-H Spec v3.0.1 Clause 1.4).

Value distributions follow the repository's synthetic TPC-H generator
(uniform keys, correlated dates, low-cardinality flags); they are synthetic,
not dbgen output.  Text columns are integer codes.
"""
from __future__ import annotations

import numpy as np

COLUMNS = [
    "l_orderkey", "l_partkey", "l_suppkey", "l_linenumber", "l_quantity",
    "l_extendedprice", "l_discount", "l_tax", "l_returnflag", "l_linestatus",
    "l_shipdate", "l_commitdate", "l_receiptdate", "l_shipinstruct",
    "l_shipmode", "l_comment"]


def generate(n: int, rng: np.random.Generator) -> np.ndarray:
    """``(n, 16)`` float64 array of float32 values, columns as :data:`COLUMNS`."""
    ship_date = rng.uniform(0, 2500, n)                      # days
    commit_date = ship_date + rng.normal(30, 15, n)          # correlated
    receipt_date = ship_date + np.abs(rng.normal(14, 7, n))
    quantity = rng.integers(1, 51, n).astype(float)
    extended_price = quantity * rng.uniform(900, 105000 / 50, n)
    discount = rng.choice(np.arange(0, 0.11, 0.01), n)
    tax = rng.choice(np.arange(0, 0.09, 0.01), n)
    order_key = np.sort(rng.uniform(0, 6e6, n))              # clustered
    part_key = rng.uniform(0, 2e5, n)
    supp_key = rng.uniform(0, 1e4, n)
    line_status = rng.integers(0, 2, n).astype(float)
    return_flag = rng.integers(0, 3, n).astype(float)
    line_number = rng.integers(1, 8, n).astype(float)
    ship_instruct = rng.integers(0, 4, n).astype(float)
    ship_mode = rng.integers(0, 7, n).astype(float)
    comment = rng.integers(0, 1 << 20, n).astype(float)
    cols = [order_key, part_key, supp_key, line_number, quantity,
            extended_price, discount, tax, return_flag, line_status,
            ship_date, commit_date, receipt_date, ship_instruct, ship_mode,
            comment]
    return np.stack(cols, axis=1).astype(np.float32).astype(np.float64)
