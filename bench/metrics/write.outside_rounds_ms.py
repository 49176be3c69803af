"""Host milliseconds per one-shot archive write outside the compressor:
the program's ``dataset.write`` span less its ``dataset.write.compress``
span (the compressor's dispatch and the wait for its results), over the
writes of the measured window.  What is left is the block planning,
encode and write (``store.append_series``) and the host work around
them."""


def read(r):
    n, write = r.hist("span.dataset.write.seconds")
    if n <= 0:
        return None
    compress = r.hist("span.dataset.write.compress.seconds")[1]
    return 1000.0 * (write - compress) / n
