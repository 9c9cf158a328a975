"""Host wall time of the per-slab scoring calls (dispatch, device run and
copy back of each slab's top-k), per flushed batch: the window's
``stage_ms{stage=score}`` sum over the batches flushed."""


def read(rec):
    count, total = rec["delta"].get("stage_ms{stage=score}", (0, 0.0))
    return total / rec["batches"] if rec["batches"] and count else None
