"""agg.group_ms: device milliseconds per global round under
the program's scope ``group_agg``.

The ops whose name-stack path (``tf_op``) holds ``group_agg`` anywhere,
by ``bench/trace.py``'s ``Reduced.scope_seconds``, averaged over the
chips, over the rounds the traced window completed. Nothing is read
where no op carries the name. Layer: group aggregation and z
(``core/engine.py`` ``group_round`` after the local phase). Moves
``round_s``.
"""

SCOPE = "group_agg"


def read(run):
    seconds = run.trace.scope_seconds(SCOPE)
    if run.rounds <= 0 or seconds is None:
        return None
    return 1e3 * seconds / run.rounds
