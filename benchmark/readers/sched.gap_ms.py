"""The host's share of the device's wait between two programs: for each
program k enqueued in the window, from the end of k's `decode.device_wait`
(the device is done, as far as the host can see) to the end of program
k+1's `decode.enqueue` (the next program is handed to the device), floored
at 0; the median, ms. A pipelined engine enqueues k+1 before it waits on k,
and reads ~0. Where k's `decode.device_wait` lasted under ~50 us the device
had finished before the host asked, and the gap reads low by the time no
host span sees: how long before the wait the device went idle."""
from benchmark.readers import _host_path


def read(ctx):
    return _host_path.gap_ms(ctx)
