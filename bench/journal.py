"""The benchmark's own reader of the program's write-ahead journal file.

The journal is a file of length-prefixed, checksummed records after a
9-byte magic (``CAMEOWAL`` and a version byte)::

    [u32 payload_len][u32 crc32(payload)][payload]

A payload starts with its type byte; a push record (type 2) is
``u8 type | u8 pad | u16 sid_len | sid | u64 start | u32 m | u16 channels |
m * max(channels, 1) float64 LE values``.  The reader stops at the first
record whose length or checksum does not hold: what follows it was never
journaled.  It imports nothing of the program.
"""
from __future__ import annotations

import struct
import zlib
from typing import Dict

import numpy as np

MAGIC = b"CAMEOWAL\x01"
RECORD = struct.Struct("<II")
PUSH_HEAD = struct.Struct("<BBH")
PUSH_BODY = struct.Struct("<QIH")
PUSH = 2


def pushes(path: str) -> Dict[str, Dict[int, np.ndarray]]:
    """The intact push records of the journal at ``path``: by series id,
    the points of each record by its start position (1-D records only)."""
    with open(path, "rb") as f:
        blob = f.read()
    if blob[:len(MAGIC)] != MAGIC:
        raise ValueError(f"{path}: not a journal")
    out: Dict[str, Dict[int, np.ndarray]] = {}
    pos = len(MAGIC)
    while pos + RECORD.size <= len(blob):
        n, crc = RECORD.unpack_from(blob, pos)
        payload = blob[pos + RECORD.size:pos + RECORD.size + n]
        if len(payload) != n or zlib.crc32(payload) != crc:
            break
        pos += RECORD.size + n
        if not payload or payload[0] != PUSH:
            continue
        _, _, k = PUSH_HEAD.unpack_from(payload, 0)
        at = PUSH_HEAD.size
        sid = payload[at:at + k].decode("utf-8")
        start, m, channels = PUSH_BODY.unpack_from(payload, at + k)
        if channels:
            continue
        at += k + PUSH_BODY.size
        out.setdefault(sid, {})[int(start)] = np.frombuffer(
            payload, "<f8", count=m, offset=at).astype(np.float64)
    return out
