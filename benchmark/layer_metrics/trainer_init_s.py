"""Seconds the program spent building its trainer and initialising its
state: the ``train.build`` (``Trainer.__init__``) and ``train.init_state``
spans as the counters stood before the window. They are cumulative since
process start, and set-up builds exactly one trainer, so this is the
program's own share of the part of ``setup_s`` that ``compile_s`` does not
cover (the rest of that part is the benchmark's weights and batches).
Layer: step. Moves ``setup_s``. A program without these spans leaves the
metric out."""


def read(run: dict):
    before = run["stages_before"]
    if "train.build" not in before or "train.init_state" not in before:
        return None
    return before["train.build"]["seconds"] + before["train.init_state"]["seconds"]
