"""95th percentile of every push's acknowledgement latency in the window,
timed by the producer from its call to its return (the ack is journaled
before return).  The producer is a closed loop that keeps the server
busy, and about one push in 85 fills a window and waits for its
compression; the rest are the push path alone (journal append, the
stream's buffer), which this tail reads."""


def read(r):
    v = r.window.get("ack_p95_ms")
    return v if v is not None and v == v else None
