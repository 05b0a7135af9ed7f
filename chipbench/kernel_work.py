"""The work one decision-kernel launch needs, and the chip's peaks.

Counted is what the *decision* needs, whatever the kernel emits, from the
states it scores (``probes.Work``), never from the padded plane: tenant
rows, state slots, partitions and frames that the program pads to its
capacities count nothing.  A launch that scores ``items`` queries (one
per frame and tenant) reads, once per tenant it scores, that tenant's
states' mins, maxs and row counts; it reads each query's bounds and writes
one float32 cost per (query, state).  Per (query, state, partition,
column) it makes two comparisons and one AND, and per (query, state,
partition) one multiply and one add of the row-weighted sum.

All values are float32 (4 bytes).  The least time is the larger of
operations over the peak operation rate and bytes over the peak HBM
bandwidth.
"""
from __future__ import annotations

from typing import Dict, Tuple

F32 = 4

#: Published peaks of one chip, keyed by ``jax.Device.device_kind``.
#: TPU v5e (Google Cloud documentation, "TPU v5e"): 197 TFLOP/s bf16,
#: 393 TOP/s int8, 16 GB of HBM at 819 GB/s.  The operation peak taken is
#: the bf16 one: the decision's comparisons run on the vector unit, whose
#: rate is lower, so the compute bound here is optimistic (the launches are
#: bound by bytes in any case).
DEVICE_PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"ops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    """The peaks of ``device_kind``; a kind not in the table is an error."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}") from None


def launch_work(items: int, item_states: int, item_partitions: int,
                plane_partitions: int, columns: int) -> Tuple[float, float]:
    """(operations, bytes) one launch needs: ``items`` queries over
    ``item_states`` states and ``item_partitions`` partitions in all, of
    tenants whose states hold ``plane_partitions`` partitions."""
    ops = item_partitions * (3.0 * columns + 2.0)
    nbytes = (plane_partitions * (2.0 * columns + 1.0)   # mins, maxs, rows
              + items * 2.0 * columns                     # query bounds
              + item_states) * F32                        # costs out
    return ops, nbytes


def ideal_seconds(ops: float, nbytes: float, device_kind: str) -> float:
    pk = peaks(device_kind)
    return max(ops / pk["ops_per_s"], nbytes / pk["hbm_bytes_per_s"])
