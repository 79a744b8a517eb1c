"""The program's own counters of its training step, as a reader sees them:
`paddle_tpu.jit.train_step_stats()`, the `stats()` of every live
`TrainStep` in order of creation. The readers hold no handle to the
trainer, and `benchmark/programs/` is the only importer of the program."""
from __future__ import annotations


def newest_train_step_stats():
    """`stats()` of the newest live `TrainStep` (the loop's own: it builds
    one and keeps it until the result line), or None where none lives or
    the program has no such accessor, as a commit before PR 24 has not."""
    import paddle_tpu.jit as jit
    accessor = getattr(jit, "train_step_stats", None)
    if accessor is None:
        return None
    live = accessor()
    return live[-1] if live else None
