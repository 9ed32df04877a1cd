"""lm.attn_ms: device milliseconds per global round under the program's
scope ``attn`` (``models/layers.py`` ``attention_block``: projections,
RoPE, attention and the output projection of every layer, forward and
backward), by ``bench/trace.py``'s ``Reduced.scope_seconds``. Nothing is
read where no op carries the name. Layer: the clients' forward and
backward. Moves ``round_s``.
"""

SCOPE = "attn"


def read(run):
    seconds = run.trace.scope_seconds(SCOPE)
    if run.rounds <= 0 or seconds is None:
        return None
    return 1e3 * seconds / run.rounds
