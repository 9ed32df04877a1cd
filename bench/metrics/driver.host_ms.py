"""driver.host_ms: host milliseconds per global round that ``fit`` spends
outside its wait for the round's metrics.

The program writes host spans on the profiler's clock: ``repro.fit``
around ``core/api.py`` ``fit`` and ``repro.fetch`` around
``core/driver.py`` ``run_rounds``' wait for the metrics. This is the time
of the window's ``repro.fit`` spans that no ``repro.fetch`` span covers
(``bench/trace.py`` ``Reduced.span_self_s``): dispatch and the rest of the
driver's host work, which the device waits for when it outlasts the
round. Nothing is read where the window holds no ``repro.fit`` span.
Layer: the device and driver. Moves ``round_s``.
"""

OUTER, INNER = "repro.fit", "repro.fetch"


def read(run):
    seconds = run.trace.span_self_s(OUTER, INNER)
    if run.rounds <= 0 or seconds is None:
        return None
    return 1e3 * seconds / run.rounds
