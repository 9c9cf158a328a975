"""Host time waiting for each slab's top-k (the device finishing and the
copy back), per flushed batch: the window's ``stage_ms{stage=slab_wait}``
sum over the batches flushed."""


def read(rec):
    count, total = rec["delta"].get("stage_ms{stage=slab_wait}", (0, 0.0))
    return total / rec["batches"] if rec["batches"] and count else None
