"""Host time in ``Planner.plan`` (vocabulary-filter verdicts and slab
sources for every segment), per flushed batch: the window's
``stage_ms{stage=plan}`` sum over the batches flushed."""


def read(rec):
    count, total = rec["delta"].get("stage_ms{stage=plan}", (0, 0.0))
    return total / rec["batches"] if rec["batches"] and count else None
