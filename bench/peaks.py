"""The chip's published peaks, keyed by JAX's ``device_kind``.

The table is ``bench/peaks.json`` with its source.  A device that is not
in it is an error, never a default: a roofline share against the wrong
peak would be a wrong number that looks right.
"""

from __future__ import annotations

import json
import pathlib

PEAKS_FILE = pathlib.Path(__file__).resolve().parent / "peaks.json"


class UnknownDevice(KeyError):
    """No peaks are recorded for this ``device_kind``."""


def peaks(device_kind: str, path: pathlib.Path = PEAKS_FILE) -> dict:
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise UnknownDevice(f"no peaks for device kind {device_kind!r} in "
                            f"{path.name}; have {sorted(table)}")
    return table[device_kind]
