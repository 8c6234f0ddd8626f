"""The whole extend step (the jitted ``extend_multi`` program): the least
time the chip needs for the corpus rows the traced window's tasks gathered
(``work.extend_bytes`` over peak HBM bandwidth, or the operations over
peak FLOP/s where larger), over the device time of every execution of the
program in the trace, in %."""
import work

PROGRAM = "jit_extend_multi"


def read(run):
    c, tr = run.traced, run.trace
    if tr is None or not c.get("tasks_emitted"):
        return None
    busy = tr.program(PROGRAM)
    if busy <= 0:
        return None
    t, _ = work.least_seconds(work.extend_flops(c["tasks_emitted"], c["dim"]),
                              work.extend_bytes(c["tasks_emitted"], c["dim"]),
                              run.peak)
    return 100.0 * t / busy
