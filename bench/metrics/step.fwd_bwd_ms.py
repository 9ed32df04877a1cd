"""step.fwd_bwd_ms: device milliseconds per global round under
the program's scope ``client_step``.

The ops whose name-stack path (``tf_op``) holds ``client_step``
anywhere, by ``bench/trace.py``'s ``Reduced.scope_seconds``, averaged
over the chips, over the rounds the traced window completed. Nothing is
read where no op carries the name. Layer: the clients' forward and
backward (``core/engine.py`` ``_client_grads``, around the client step,
packed or vmapped). Moves ``round_s``.
"""

SCOPE = "client_step"


def read(run):
    seconds = run.trace.scope_seconds(SCOPE)
    if run.rounds <= 0 or seconds is None:
        return None
    return 1e3 * seconds / run.rounds
