"""pack.repack_ms: device milliseconds per global round under
the program's scope ``state_repack``.

The ops whose name-stack path (``tf_op``) holds ``state_repack``
anywhere, by ``bench/trace.py``'s ``Reduced.scope_seconds``, averaged
over the chips, over the rounds the traced window completed. Nothing is
read where no op carries the name. Layer: the flat ``[G, K, N]`` state's
repacks (``core/packer.py`` ``Packer.flatten``/``unflatten``). Moves
``round_s``.
"""

SCOPE = "state_repack"


def read(run):
    seconds = run.trace.scope_seconds(SCOPE)
    if run.rounds <= 0 or seconds is None:
        return None
    return 1e3 * seconds / run.rounds
