"""Readings that the limits of a cell's comparison are set from.

    python3 -m bench.calibrate --workload <cell> --seeds <n> [<n> ...] \\
        [--control K] [--faults K] [--highest K] [--out FILE]

On the chip, at the cell's own size, in one process. For every seed it sets
the cell up and drives the program's checked calls exactly as a run does,
then trains the same rounds with the plain reference, in the configuration's
precision, and prints every number of ``check.NUMBERS`` of:

* ``sound``: the program against the reference (every seed): the largest
  over a dozen seeds or more is a limit's lower reading;
* ``control``: the reference computed in bfloat16, the precision below the
  configuration's float32, put in the program's place (the first K seeds);
* ``half_batch``: the reference with half of every batch left out and the
  mean taken over the rest, in the program's place, and ``no_y``: the
  reference with y left out of the local step (the first K seeds);
* ``highest``: the reference with float32 products (``"highest"``) in the
  program's place (the first K seeds): how far the precision of the
  products alone moves each number.

A step that returns its state unchanged reads 1 in every number but
``loss`` by the way they are measured, and needs no run. One JSON line per seed, then
a summary line; ``--out`` appends them to a file as well.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time

from bench import run as brun


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--faults", type=int, default=3)
    ap.add_argument("--highest", type=int, default=0)
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    sys.path.insert(0, str(brun.ROOT / "src"))
    cell = brun.load_cell(args.workload)
    try:
        brun.check_devices(cell.workload["chips"])
    except brun.NoAccelerator as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 3
    brun.enable_compile_cache()
    import jax

    from bench import check

    out = open(args.out, "a") if args.out else None

    def emit(obj):
        line = json.dumps(obj)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    others = {  # reference_readout's arguments for each kind in its place
        "control": ({"dtype": "bfloat16"}, args.control),
        "half_batch": ({"batch_fraction": 0.5}, args.faults),
        "no_y": ({"drop_y": True}, args.faults),
        "highest": ({"precision": "highest"}, args.highest),
    }
    kinds = {"sound": [], **{k: [] for k in others}}
    for i, seed in enumerate(args.seeds):
        t = time.perf_counter()
        fed, engine, data, state, x0 = brun.set_up(cell, seed)
        state, data, program = brun.drive_checked(cell, engine, data, state)
        jax.block_until_ready(state)
        del state, data, engine
        gc.collect()
        row = {"seed": seed, "program_s": time.perf_counter() - t}
        t = time.perf_counter()
        ref = brun.reference_readout(cell, seed, fed, x0)
        row["reference_s"] = time.perf_counter() - t
        row["sound"] = check.readings(x0, program, ref)
        for kind, (kwargs, first_k) in others.items():
            if i < first_k:
                other = brun.reference_readout(cell, seed, fed, x0, **kwargs)
                row[kind] = check.readings(x0, other, ref)
        for kind in kinds:
            if kind in row:
                kinds[kind].append(row[kind])
        emit(row)
        del fed, x0, program, ref
        gc.collect()

    summary = {"workload": args.workload, "seeds": len(args.seeds)}
    for n in check.NUMBERS:
        summary[n] = {"sound_max": max(r[n] for r in kinds["sound"])}
        for kind in others:
            summary[n][f"{kind}_min"] = min((r[n] for r in kinds[kind]),
                                            default=None)
    emit({"summary": summary})
    return 0


if __name__ == "__main__":
    sys.exit(main())
