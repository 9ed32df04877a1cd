"""update.ms: device milliseconds per global round under
the program's scope ``local_update``.

The ops whose name-stack path (``tf_op``) holds ``local_update``
anywhere, by ``bench/trace.py``'s ``Reduced.scope_seconds``, averaged
over the chips, over the rounds the traced window completed. Nothing is
read where no op carries the name. Layer: the corrected local update,
x <- x - lr (g + z + y) (``core/engine.py``: tree, flat and fused
phases). Moves ``round_s``.
"""

SCOPE = "local_update"


def read(run):
    seconds = run.trace.scope_seconds(SCOPE)
    if run.rounds <= 0 or seconds is None:
        return None
    return 1e3 * seconds / run.rounds
