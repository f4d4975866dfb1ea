"""Host milliseconds per staged batch to pack it, issue its transfer and
wait for the transfer to complete: the program's own ``input_stages``
counters (``stage`` + ``transfer`` seconds) after the window minus before
it, over the batches staged in between. Layer: input. Moves
``examples_per_s``. A staged batch is one dispatch's worth (K steps in a
fused loop)."""


def read(run: dict):
    before, after = run["stages_before"], run["stages_after"]

    def delta(stage: str, key: str) -> float:
        return after.get(stage, {}).get(key, 0) - before.get(stage, {}).get(key, 0)
    batches = delta("stage", "count")     # the coalesced stager counts its packs
    if batches <= 0:                      # the per-leaf path: one transfer a batch
        batches = delta("transfer", "count")
    if batches <= 0:
        return None
    return 1e3 * (delta("stage", "seconds") + delta("transfer", "seconds")) / batches
