#!/usr/bin/env python3
"""Read a cell's compared numbers for the program and for its control.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds <n> ...

For each seed, in one process: make the data, build and warm the program
as a run does, drive a window of ``--seconds`` at the cell's own load,
free the program's state, then compare the program's answers with the
reference, and compare the control's answers with the reference in
their place.  The control is the configuration's ``control_lookup``: the
reference with one guarantee that the configuration states broken.  One
JSON line per seed: ``{"seed", "program": {name: value}, "control":
{name: value}, "limits": {name: limit}}``.  The benchmark's own runs do
not run it.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys

import run


def read_seeds(workload: str, seeds, seconds: float, *, root: str = run.ROOT,
               require_tpu: bool = True):
    bench = run.Bench(root)
    cell = bench.cell(workload)
    cfg, cfgmod = bench.config(cell["config"])
    mix, loopmod = bench.mix(cell["traffic"])
    run.start_jax(require_tpu, int(cell["chips"]))
    for seed in seeds:
        data = cfgmod.make(cfg, seed)
        loop = loopmod.Loop(cfg, cfgmod, data, mix, seed,
                            lambda name: contextlib.nullcontext())
        loop.setup(seconds)
        loop.window(seconds)
        loop.release()
        prog = loop.check()
        ctrl = loop.check(control=cfgmod.control_lookup)
        yield {"seed": seed, "program": {n: v for n, v, _ in prog},
               "control": {n: v for n, v, _ in ctrl},
               "limits": {n: lim for n, _, lim in prog}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    try:
        for line in read_seeds(args.workload, args.seeds, args.seconds):
            print(json.dumps(line), flush=True)
    except run.NoChip as e:
        print(e, file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
