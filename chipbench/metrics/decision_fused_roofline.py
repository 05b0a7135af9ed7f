"""Decision kernel: the least time its launches in the window need (from
the states each scores, ``kernel_work.py``) over the device time of its
operations in the trace.  Nothing is read when a launch's work is unknown
(made outside a watched scoring call)."""
from chipbench import kernel_work


def read(run):
    trace = run.trace
    if (trace is None or trace.kernel_s <= 0 or not run.launches
            or any(w is None for w in run.launches)):
        return None
    ideal = sum(kernel_work.ideal_seconds(
        *kernel_work.launch_work(w.items, w.item_states, w.item_partitions,
                                 w.plane_partitions, w.columns),
        run.device_kind) for w in run.launches)
    return 100.0 * ideal / trace.kernel_s
