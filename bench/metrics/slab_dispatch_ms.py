"""Host time in the call of each slab's jitted scoring program until it
returns, per flushed batch: the window's ``stage_ms{stage=slab_dispatch}``
sum over the batches flushed."""


def read(rec):
    count, total = rec["delta"].get("stage_ms{stage=slab_dispatch}",
                                    (0, 0.0))
    return total / rec["batches"] if rec["batches"] and count else None
