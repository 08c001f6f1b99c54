#!/usr/bin/env python3
"""The control of the comparison, on the chip at a cell's own sizes.

    python3 bench/control.py --workload <name> --seeds 1,2,3 \\
        [--seconds 10]

For each seed, one whole run of the cell (``harness.run``) whose
comparison takes the control's logits in the served ones' place: the
reference with every convolution and classifier operand in float8
e4m3 (``reference.logits(control=True)``), one step below the
configuration's bfloat16.  It has to come out ``correct: false``; its
``logit_gap`` is the upper reading of the configuration's limit.  The
seeds run in one process, so set-up is paid once for what the compile
cache keeps.  Prints one JSON line per seed.  The benchmark's own runs
never run it.
"""
from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=10.0)
    opts = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    from bench import harness

    for seed in (int(s) for s in opts.seeds.split(",")):
        out = harness.run(ROOT, opts.workload, seed, opts.seconds, False,
                          t_process=time.monotonic(), control=True)
        print(json.dumps({"workload": opts.workload, "seed": seed,
                          "control_correct": out["correct"],
                          "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
