"""The chip's memory bandwidth, for the readers that turn bytes a kernel
had to move and the time it took into a share of a roofline:
``peaks_hbm.json`` beside ``peaks.json``, keyed as ``flops.peak_flops``
keys that table, and the bytes of a stored value."""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))

#: bytes of one stored value, by a configuration's ``torch_dtype``
DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def peak_bytes_per_s(device_kind: str) -> float:
    """HBM bytes/s of one chip from ``peaks_hbm.json``, keyed by a
    substring of ``device_kind``; an unknown kind is an error, not a
    default. ``run.py`` refuses to run off a TPU, so the kind ``cpu`` is
    only ever the CPU rehearsal's, whose shares are plumbing and not
    numbers: it is handed the table's first row."""
    with open(os.path.join(_HERE, "peaks_hbm.json")) as f:
        table = json.load(f)
    for row in table["peaks"]:
        if row["match"] in device_kind.lower() or device_kind == "cpu":
            return float(row["hbm_bytes_per_s"])
    raise ValueError(
        f"no memory bandwidth on record for device_kind {device_kind!r}: "
        "add it to benchmark/peaks_hbm.json with its source"
    )
