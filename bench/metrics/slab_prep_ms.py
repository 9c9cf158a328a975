"""Host time spent preparing each slab's scoring call (L-bucket pad, the
merged query stream, capacity pad, norms and their upload), per flushed
batch: the window's ``stage_ms{stage=slab_prep}`` sum over the batches
flushed."""


def read(rec):
    count, total = rec["delta"].get("stage_ms{stage=slab_prep}", (0, 0.0))
    return total / rec["batches"] if rec["batches"] and count else None
