"""A whole run on the CPU, past the harness's look for a chip: sound, it
is correct; with an answer altered where the grid cell produces it, or
with half of each batch left out of the step, the comparison says
``correct: false``."""
import os
import time
from pathlib import Path


ROOT = Path(__file__).resolve().parents[2]
WORKLOAD = "paper-cifar32.coef.closed"


def _run(tmp_path, hook=None):
    from bench import harness

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    return harness.run(ROOT, WORKLOAD, 2 ** 32 + 99, 1.0, False,
                       t_process=time.monotonic(), require_chips=False,
                       system_hook=hook)


def _alter_one_answer(sched):
    """Every grid cell adds 1 to the first logit of its first row."""
    for col in sched.grid_engine.distinct:
        for cell in col.cells.values():
            fn = cell._fn
            cell._fn = (lambda f: lambda x: f(x).at[0, 0].add(1.0))(fn)


def _leave_out_half_the_batch(sched):
    """Every grid cell computes its batch's first half only: the rows
    of the second half go in as zeros."""
    for col in sched.grid_engine.distinct:
        for cell in col.cells.values():
            fn = cell._fn
            cell._fn = (lambda f: lambda x: f(
                x.at[x.shape[0] // 2:].set(0)))(fn)


def test_sound_run_is_correct(tmp_path):
    out = _run(tmp_path)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"images_per_s", "p50_ms", "p95_ms",
                                   "setup_s"}
    assert list(out)[-1] == "checks"


def test_altered_answer_is_not_correct(tmp_path):
    out = _run(tmp_path, _alter_one_answer)
    assert not out["correct"]
    gap = out["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]


def test_half_batch_left_out_is_not_correct(tmp_path):
    out = _run(tmp_path, _leave_out_half_the_batch)
    assert not out["correct"]
    gap = out["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]
