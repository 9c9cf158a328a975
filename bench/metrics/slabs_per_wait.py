"""Slabs dispatched per blocking copy back of their top-k: the window's
``stage_ms{stage=slab_dispatch}`` count over its
``stage_ms{stage=slab_wait}`` count. 1.0 where every slab is waited on
alone; the number of slabs in a pass where a pass waits once."""


def read(rec):
    waits = rec["delta"].get("stage_ms{stage=slab_wait}", (0, 0.0))[0]
    dispatches = rec["delta"].get("stage_ms{stage=slab_dispatch}",
                                  (0, 0.0))[0]
    return dispatches / waits if waits else None
