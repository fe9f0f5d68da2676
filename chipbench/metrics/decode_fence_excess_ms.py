"""What the D2H fence of a decode step waits beyond the device's own
work: mean phase ``fence`` of the window's ``step`` records less the
decode executable's device time in the trace. Launch latency, the copy
back and the thread's wake-up; where an admission's join is still on the
device when the step is dispatched, its tail as well. The device time is
the MEDIAN of the traced launches: the capture's edges clip the launch in
flight when it starts and the one in flight when it stops, and their
stumps pull a mean of 15-30 launches down by up to 4 ms."""
from chipbench import ledger, stats
from chipbench.reduce import module_times


def read(record):
    device = stats.median(module_times(record, "decode") or [])
    fence = ledger.mean(ledger.phase_sums(record, "step", "fence"))
    if device is None or fence is None:
        return None
    return 1e3 * (fence - device)
