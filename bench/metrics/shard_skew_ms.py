"""How long the gather waits on the slowest shard of a batch beyond a
typical one: the window's mean ``cluster_straggler_ms`` (the slowest
shard's wall time, one a batch) less the mean ``stage_ms{stage=shard}``.
Nothing to read without both."""


def read(rec):
    slow_n, slow_sum = rec["delta"].get("cluster_straggler_ms{}", (0, 0.0))
    shard_n, shard_sum = rec["delta"].get("stage_ms{stage=shard}", (0, 0.0))
    if not slow_n or not shard_n:
        return None
    return slow_sum / slow_n - shard_sum / shard_n
