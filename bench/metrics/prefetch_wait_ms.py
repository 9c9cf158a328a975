"""Time the scan loop waited on the prefetcher for a slab (mmap read,
decode, upload), per flushed batch: the window's
``stage_ms{stage=prefetch_wait}`` sum over the batches flushed."""


def read(rec):
    count, total = rec["delta"].get("stage_ms{stage=prefetch_wait}",
                                    (0, 0.0))
    return total / rec["batches"] if rec["batches"] and count else None
