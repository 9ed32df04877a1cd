"""step.groupnorm_ms: device milliseconds per global round under
the program's scope ``groupnorm``.

The ops whose name-stack path (``tf_op``) holds ``groupnorm`` anywhere,
by ``bench/trace.py``'s ``Reduced.scope_seconds``, averaged over the
chips, over the rounds the traced window completed. Nothing is read
where no op carries the name: the CNN has no GroupNorm, and a program
without the scope names none. Layer: the clients' forward and backward,
the client-packed GroupNorm's forward and its analytic backward
(``models/small.py`` ``_gn_packed``), inside ``client_step``. Moves
``round_s``.
"""

SCOPE = "groupnorm"


def read(run):
    seconds = run.trace.scope_seconds(SCOPE)
    if run.rounds <= 0 or seconds is None:
        return None
    return 1e3 * seconds / run.rounds
