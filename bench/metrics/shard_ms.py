"""Wall time of one shard's whole search on its router thread (plan, scan
and merge of that shard, on its chip), mean over the shard searches of
the window: the window's ``stage_ms{stage=shard}`` sum over its count.
Nothing to read where the router has no ``shard`` stage."""


def read(rec):
    count, total = rec["delta"].get("stage_ms{stage=shard}", (0, 0.0))
    return total / count if count else None
