"""Share of slab lookups the device slab cache served, in %: the window's
``cache_hits_total`` / (``cache_hits_total`` + ``cache_misses_total``)
under the label of the surface the run serves (``store``: the session's
own count; ``cluster``: the router's sum over the shards it gathered)."""


def read(rec):
    d, surface = rec["delta"], rec["surface"]
    hits = d.get(f"cache_hits_total{{surface={surface}}}", (0,))[0]
    miss = d.get(f"cache_misses_total{{surface={surface}}}", (0,))[0]
    return 100.0 * hits / (hits + miss) if hits + miss else None
