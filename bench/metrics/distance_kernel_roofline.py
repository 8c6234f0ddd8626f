"""The Pallas distance kernel (``kernels/distance.py``): the least time the
chip needs for the corpus rows the traced window's tasks gathered, over the
kernel's own device time in the trace, in %. Bytes bound it: a task reads
a row of d float32 and does 3d operations."""
import work

KERNEL = r"distance_kernel|distance_tasks"


def read(run):
    c, tr = run.traced, run.trace
    if tr is None or not c.get("tasks_emitted"):
        return None
    busy = tr.ops(KERNEL)
    if busy <= 0:
        return None
    t, _ = work.least_seconds(work.extend_flops(c["tasks_emitted"], c["dim"]),
                              work.extend_bytes(c["tasks_emitted"], c["dim"]),
                              run.peak)
    return 100.0 * t / busy
