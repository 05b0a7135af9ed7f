"""Table generators, one module per source table, found by name."""
from __future__ import annotations

import importlib

import numpy as np


def make(table: str, rows: int, seed: int, tenants: int, k: int
         ) -> np.ndarray:
    """Tenant ``k``'s table: ``rows`` rows of ``table`` from its own stream
    of ``seed``."""
    gen = importlib.import_module(f"chipbench.tables.{table}").generate
    stream = np.random.SeedSequence([seed, 1]).spawn(tenants)[k]
    return gen(rows, np.random.default_rng(stream))
