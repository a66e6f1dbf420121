"""Published peaks of one chip, one file per `device_kind` under
peaks/ (spaces in the kind become `_`).  A device without a file is an
error, not a default."""

from __future__ import annotations

import json
import os

_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "peaks")


def peaks_for(device_kind: str) -> dict:
    path = os.path.join(_DIR, device_kind.replace(" ", "_") + ".json")
    if not os.path.exists(path):
        raise KeyError(f"no peaks recorded for device kind {device_kind!r}: "
                       f"add {path} with its source")
    with open(path) as f:
        return json.load(f)
