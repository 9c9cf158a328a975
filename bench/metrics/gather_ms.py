"""The router's wall time per flushed batch, from the scatter's submission
to the merged answer (the wait on the slowest shard and the shard-order
fold): the window's ``stage_ms{stage=gather}`` sum over the batches
flushed. Nothing to read where the router has no ``gather`` stage."""


def read(rec):
    count, total = rec["delta"].get("stage_ms{stage=gather}", (0, 0.0))
    return total / rec["batches"] if rec["batches"] and count else None
