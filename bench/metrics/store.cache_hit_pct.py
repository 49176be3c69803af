"""Decoded-block cache hit share over the measured window (the program's
``store.cache.hits`` and ``store.cache.misses`` counters)."""


def read(r):
    hits, misses = r.counter("store.cache.hits"), r.counter("store.cache.misses")
    if hits + misses <= 0:
        return None
    return 100.0 * hits / (hits + misses)
