"""The work an operation needs, whatever implements it, and the chip's
peaks.  Roofline shares are read against these (bench/reducers/roofline.py).

Needed work counts what the operation must touch, not what the program
does: a lookup must read its query key, write its result, and read the
one place its key can live; a recovery scan must read every slot's
persisted stage, key and value.
"""
from __future__ import annotations

import json
import os

WORD = 4                    # bytes of a key, an id or a stage
PROBE_CHUNK = 16            # probe slots a lookup reads at a time


def lookup_bytes(config: dict) -> dict:
    """One lookup: query key in, node id out, plus one home bucket row
    (W ways of key and id) for the bucket index, or one probe chunk of
    16 slots (id and key each) for the linear-probe index."""
    if config["backend"] == "bucket":
        place = config.get("bucket_width", 8) * 2 * WORD
    else:
        place = PROBE_CHUNK * 2 * WORD
    return {"bytes": 2 * WORD + place}


def recover_bytes(config: dict) -> dict:
    """One slot of a full recovery: its persisted stage, key and value
    words, each read once."""
    return {"bytes": 3 * WORD}


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; an unknown device is an error."""
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add it "
                       f"to bench/peaks.json with its source")
    return table["devices"][device_kind]
