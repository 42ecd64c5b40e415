#!/usr/bin/env python3
"""Run the control of ``correct`` on the chip: a cell's own run, with every
lookup answered from a 16-bit value column (``faults.values_in_16_bits``).

    python3 bench/control.py --workload <cell> --seeds 11,12,13 --seconds 10

It builds the cell once per seed, in one process, and prints one JSON line
per seed with the numbers ``correct`` compares.  The control must come out
not correct on every seed.  The benchmark's own runs never run it.
"""
import time

T_START = time.perf_counter()

import argparse      # noqa: E402
import gc            # noqa: E402
import json          # noqa: E402
import sys           # noqa: E402

import run           # noqa: E402  (puts bench/ and src/ on the path)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)

    import faults
    import harness
    import jax
    cell = harness.load_cell(args.workload)
    if jax.default_backend() != "tpu":
        run.log(f"control: needs a TPU, JAX found {jax.default_backend()!r}")
        return 2
    run.enable_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        out = harness.run_cell(cell, seed, args.seconds, False,
                               t_start=time.perf_counter(), log=run.log,
                               tamper=faults.values_in_16_bits)
        print(json.dumps(dict(workload=cell.name, seed=seed,
                              correct=out["correct"],
                              checks={k: v for k, (v, _) in
                                      out["checks"].items()},
                              attempted=out["attempted"])), flush=True)
        del out
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
