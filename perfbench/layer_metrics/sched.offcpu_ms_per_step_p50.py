"""The time the scheduler's thread neither ran nor waited for the device
in a ``decode`` step of the window: the median step's wall time less its
``wait`` phase (``sched.host_ms_per_step_p50``'s quantity, flight records
``dur_ms - wait_ms``), less the steps' mean CPU time of that thread
(``cpu_ms``). It is the interpreter held by another thread, or a block
inside a runtime call that no ``wait`` phase covers.

The mean, not each step's own number: the thread's clock is the host
kernel's, and on the chip's host it moves in ticks of 10 ms, so a step of
6-20 ms reads 0.0 or 10.0 and only a sum over many steps says what the
thread used. Records from before ``cpu_ms`` give nothing to read."""

from perfbench.loadgen import flight_records, percentile


def read(ctx):
    steps = [
        r for r in flight_records(ctx)
        if r["mode"] == "decode" and "cpu_ms" in r and "wait_ms" in r
    ]
    if not steps:
        return None
    host = percentile([r["dur_ms"] - r["wait_ms"] for r in steps], 0.5)
    return host - sum(r["cpu_ms"] for r in steps) / len(steps)
