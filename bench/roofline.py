"""The chip's peaks and the roofline share of a kernel.

``peaks.json`` holds the published peaks, keyed by JAX's ``device_kind``;
a device that is not there is an error, never a default.  No float32 FLOP
peak is published for v5e, so float32 work is held against the bf16 peak,
which can only make the FLOP bound looser (the share lower), never above
100%.
"""
from __future__ import annotations

import json
from pathlib import Path

_PEAKS = Path(__file__).resolve().parent / "peaks.json"


class UnknownDevice(KeyError):
    pass


def peaks(device_kind: str) -> dict:
    table = json.loads(_PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise UnknownDevice(f"no published peaks for device kind "
                            f"{device_kind!r}; known: {sorted(table)}")
    return table[device_kind]


def share(flops: float, nbytes: float, seconds: float,
          device_kind: str) -> tuple:
    """``(percent, bound)``: the least time the chip could take for the
    work, the larger of FLOPs over peak FLOP/s and bytes over peak bytes/s,
    as a share of the measured ``seconds``; ``bound`` says which of the two
    sets that least time (``"flops"`` or ``"bytes"``)."""
    if seconds <= 0:
        raise ValueError(f"kernel time {seconds} s")
    p = peaks(device_kind)
    t_flops = flops / p["bf16_flops_per_s"]
    t_bytes = nbytes / p["hbm_bytes_per_s"]
    bound = "flops" if t_flops >= t_bytes else "bytes"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
