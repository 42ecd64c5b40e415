#!/usr/bin/env python3
"""Run one benchmark cell once, on the TPU it is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``.  The run builds
the cell's deployment from ``--seed``, warms up its own shapes, drives the
served path for ``--seconds``, checks every answer against the plain
reference once the window has closed, and prints, as the last line of
standard output, one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics, read from a profiler trace of the window), ``device``
and, last, ``checks``: each number compared with its limit.  The same
numbers close standard error.

Without a TPU, or with fewer chips than the cell asks for, it exits with
code 2 and prints no result: there is no CPU path.
"""
import time

T_START = time.perf_counter()

import argparse      # noqa: E402
import json          # noqa: E402
import os            # noqa: E402
import shutil        # noqa: E402
import sys           # noqa: E402
import tempfile      # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def enable_compile_cache() -> str:
    """JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR`` where set,
    else the fixed ``<checkout>/.jax_cache``.  Every program is kept, so a
    second run of a cell compiles nothing."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[1])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seconds <= 0:
        ap.error("--seconds must be positive")

    import harness
    cell = harness.load_cell(args.workload)
    import jax
    if jax.default_backend() != "tpu":
        log(f"bench: needs a TPU, JAX found {jax.default_backend()!r}")
        return 2
    if len(jax.devices()) < cell.chips:
        log(f"bench: {cell.name} needs {cell.chips} chips, JAX found "
            f"{len(jax.devices())}")
        return 2
    log(f"bench: {cell.name} seed {args.seed}, device "
        f"{harness.device_info()}, compile cache {enable_compile_cache()}")

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace \
        else None
    try:
        out = harness.run_cell(cell, args.seed, args.seconds,
                               bool(args.trace), t_start=T_START, log=log,
                               trace_dir=trace_dir)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    checks = out.pop("checks")
    compiles = out.pop("compiles")
    log(f"bench: {compiles} compiles in the window")
    for k, v in out["metrics"].items():
        log(f"bench: {k} = {v['value']!r} {v['unit']}")
    line = dict(correct=out["correct"], attempted=out["attempted"],
                failed=out["failed"], metrics=out["metrics"],
                device=out["device"])
    if "breakdown" in out:
        line["breakdown"] = out["breakdown"]
    line["checks"] = {k: dict(value=v, limit=lim)
                      for k, (v, lim) in checks.items()}
    log(f"bench: correct = {out['correct']}")
    for k, (v, lim) in checks.items():
        log(f"check {k}: {v} (limit {lim})")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
