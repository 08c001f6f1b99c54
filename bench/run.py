#!/usr/bin/env python3
"""Chip benchmark of the JPEG-domain ResNet serving path: one run of one
cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

Run from the root of a checkout (``BENCHMARK.json`` beside ``bench/`` and
``src/``).  Prints progress and the compared numbers with their limits on
standard error, and one JSON object as the last line of standard output:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics with ``--trace 0``, its per-layer metrics with
``--trace 1``), ``device``, ``breakdown`` (traced runs) and ``checks``.
Exits non-zero, printing no result, without a TPU with the chips the
cell asks for, or without the program beside it.  JAX's compile cache is
``.jax_cache/`` in the checkout.
"""
import os
import sys
import time

T_PROCESS = time.monotonic()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", metavar="DIR",
                    help="copy a traced run's profile here")
    opts = ap.parse_args(argv)
    src = os.path.join(ROOT, "src")
    if not os.path.isfile(os.path.join(src, "repro", "serving",
                                       "scheduler.py")):
        print(f"bench: no program under {src}; nothing to measure",
              file=sys.stderr)
        return 2
    sys.path[:0] = [ROOT, src]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT,
                                                           ".jax_cache")
    import json
    from pathlib import Path

    from bench import harness

    out = harness.run(Path(ROOT), opts.workload, opts.seed, opts.seconds,
                      bool(opts.trace), t_process=T_PROCESS,
                      keep_trace=opts.keep_trace)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
