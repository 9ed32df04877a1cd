"""device.idle_share: the share of the traced window in which the device
ran no operation, in percent.

1 - (union of the device's op intervals in the window / the window), the
window being the run's own host span around its measured ``fit`` calls,
averaged over the chips (``bench/trace.py``). Layer: the driver that feeds
the device (``core/driver.py`` ``run_rounds``/``dispatch_chunk``) and the
host work between ``fit`` calls. Moves ``round_s``.
"""


def read(run):
    if run.trace.window_s <= 0 or not any(run.trace.ops.values()):
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
