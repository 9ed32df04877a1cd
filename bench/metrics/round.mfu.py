"""round.mfu: model FLOP/s of the traced window over the chips' peak, in
percent.

The FLOPs are the model's: the configuration's ``forward_flops`` per
sample (2 x the multiply-adds of each conv and dense layer), times 3 for
forward plus backward, times every sample of every active client's local
steps in the rounds the window completed. The time is the traced window's
wall time, times the chips, times the bf16 peak of the device kind
(``bench/peaks.py``): float32 matmuls at default precision run as one bf16
pass on the MXU. Layer: the clients' forward and backward
(``core/engine.py`` ``_build_global_round``, ``models/small.py``). Moves
``round_s``.
"""


def read(run):
    if run.rounds <= 0 or run.trace.window_s <= 0:
        return None
    peak = run.peaks["bf16_flops_per_s"] * run.chips
    return 100.0 * run.flops_per_round * run.rounds / (run.trace.window_s * peak)
