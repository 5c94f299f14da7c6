"""Readings that the limits of a training cell's check are set from.

    python3 bench/readings.py --workload <cell> --seeds 1,2,... \
        [--control-seeds 7,8,9] [--out <file.json>]

For each seed of ``--seeds`` the program runs the cell's first steps at
the cell's own size, through the window's own call, and is compared with
the float32 reference (the lower readings).  For each seed of
``--control-seeds`` two stand-ins are compared with the float32 reference
the same way: the reference computed in bfloat16 (the control), and the
reference with half of every batch left out of the loss, the mean taken
over the rest (a planted fault).  A step that returns its state unchanged
reads 1 on ``grad_gap`` and ``delta_gap`` by construction and needs no
run.  The benchmark's own runs never run this; it needs the chip, like
them.
"""
from __future__ import annotations

import argparse
import importlib
import json
import sys
import time

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    run.enable_cache()
    try:
        run.chip_devices(cell.chips)
    except run.NoChip as e:
        run.log(f"readings: {e}")
        return 2
    from benchlib import training_check as tc
    mod = importlib.import_module(f"drivers.{cell.traffic['driver']}")
    n = cell.traffic["first_steps"]
    out = {"workload": args.workload, "program": {}, "control": {},
           "half_batch": {}}
    for seed in [int(s) for s in args.seeds.split(",")]:
        t = time.perf_counter()
        drv = mod.Driver(cell.config, cell.traffic, seed)
        drv.first_steps(n)
        prog = drv.first
        drv.release()
        ref = mod.reference_first_steps(cell.config, cell.traffic, seed)
        out["program"][seed] = dict(tc.numbers(prog, ref),
                                    losses=prog.losses,
                                    ref_losses=ref.losses)
        run.log(f"program seed {seed}: {out['program'][seed]} "
                f"({time.perf_counter() - t:.1f} s)")
    for seed in [int(s) for s in args.control_seeds.split(",") if s]:
        ref = mod.reference_first_steps(cell.config, cell.traffic, seed)
        for kind, kw in (("control", {"dtype": "bfloat16"}),
                         ("half_batch", {"fault": "half_batch"})):
            other = mod.reference_first_steps(cell.config, cell.traffic,
                                              seed, **kw)
            out[kind][seed] = tc.numbers(other, ref)
            run.log(f"{kind} seed {seed}: {out[kind][seed]}")
    text = json.dumps(out, indent=1)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text)
    print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
