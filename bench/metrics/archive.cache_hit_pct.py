"""Decoded-block cache hit share in the archive cell, where a write drops
only its own series' entries (``store.cache_hit_pct``'s reading over the
measured window)."""
from bench import metrics


def read(r):
    return metrics.reader("store.cache_hit_pct")(r)
