"""Roofline share of the ranking kernel ``window_rows_pallas``: the least
time the chip could take for the calls traced (operations and bytes from
``kernels/window_rows.py``, peaks from ``peaks.json``) over their device
time in the trace.  Only calls wholly inside the traced part count.  The
bound that applies (``flops`` or ``bytes``) goes to ``notes``."""
from bench import roofline, trace as tr
from bench.kernels import window_rows as kernel


def read(r):
    t = r.trace
    if t is None:
        return None
    lo, hi = t.window
    flops = nbytes = secs = 0.0
    for events in t.ops.values():
        for text, s, e in events:
            if not (s >= lo and e <= hi and tr.instruction_name(text)
                    .startswith(kernel.INSTRUCTION)):
                continue
            ops, res = tr.operand_shapes(text), tr.result_shape(text)
            if ops is None or res is None:
                continue
            f, b = kernel.cost(ops, res)
            flops, nbytes, secs = flops + f, nbytes + b, secs + (e - s) * 1e-9
    if secs <= 0:
        return None
    pct, bound = roofline.share(flops, nbytes, secs, r.device_kind)
    r.notes["window_rows_roofline"] = {
        "bound": bound, "flops": flops, "bytes": nbytes, "kernel_s": secs}
    return pct
