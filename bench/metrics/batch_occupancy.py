"""Mean coalesced batch size over the window: queries flushed / batches
flushed by the ``SearchService`` coalescer (``BatcherStats``)."""


def read(rec):
    if not rec["batches"]:
        return None
    return rec["requests"] / rec["batches"]
