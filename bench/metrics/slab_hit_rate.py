"""Share of slab lookups the device slab cache served, in %: the window's
``cache_hits_total`` / (``cache_hits_total`` + ``cache_misses_total``) of
the store surface."""


def read(rec):
    d = rec["delta"]
    hits = d.get("cache_hits_total{surface=store}", (0,))[0]
    miss = d.get("cache_misses_total{surface=store}", (0,))[0]
    return 100.0 * hits / (hits + miss) if hits + miss else None
