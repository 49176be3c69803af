"""Mean group-commit fsync time of the write-ahead journal over the
measured window (the program's ``wal.fsync_seconds`` histogram)."""


def read(r):
    count, total = r.hist("wal.fsync_seconds")
    if count <= 0:
        return None
    return 1000.0 * total / count
