"""layout.copy_ms: device milliseconds per global round spent in data
formatting: moving bytes into another shape or layout without arithmetic.

The TPU trace gives no category per operation, so the rule reads the
instruction (``bench/trace.py``): an instruction counts when its opcode is
one of ``FORMAT_OPS``, or when it is a fusion whose name is made only of
``FORMAT_WORDS`` (XLA names a fusion after the instructions it fuses, as in
``constant_dynamic-slice_fusion``). Async DMAs in flight beside the compute
are not counted; the ``copy-done`` that waits for one is. This takes in the
repacks of the flat ``[G, K, N]`` state (``core/packer.py`` flatten and
unflatten: slices, reshapes, dynamic-update-slices of the whole model) and
the relayout copies and reshapes of the convolutions' activations, which
on the chip are most of it. Layer: data formatting, both kinds. Moves
``round_s``.
"""

import re

FORMAT_OPS = frozenset({
    "copy", "copy-start", "copy-done", "transpose", "reshape", "pad",
    "slice", "dynamic-slice", "dynamic-update-slice", "concatenate",
})
FORMAT_WORDS = FORMAT_OPS | {"bitcast", "constant"}


def is_format(text):
    from bench.trace import instruction, opcode

    op = opcode(text)
    if op in FORMAT_OPS:
        return True
    if op != "fusion":
        return False
    words = [w for w in re.split(r"_", instruction(text)) if w != "fusion"]
    return bool(words) and all(w in FORMAT_WORDS for w in words)


def read(run):
    if run.rounds <= 0 or not any(run.trace.ops.values()):
        return None
    seconds = sum(s for name, s in run.trace.op_seconds().items()
                  if is_format(name))
    return 1e3 * seconds / run.rounds
