"""Time the slab-prefetch thread spent reading and decoding slabs from
the store (mmap page-ins and ``decode_to_ell``), per flushed batch: the
window's ``stage_ms{stage=decode}`` sum over the batches flushed. Nothing
to read where every slab is a cache hit."""


def read(rec):
    count, total = rec["delta"].get("stage_ms{stage=decode}", (0, 0.0))
    return total / rec["batches"] if rec["batches"] and count else None
