"""The chip's published peaks: one file per `device_kind` under
benchmark/peaks/, named by the kind with every character outside
[A-Za-z0-9_.-] replaced by `_`.  A kind without a file is an error, never a
default."""

import json
import os
import re

PEAKS_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "peaks")


def peaks_for(device_kind):
    path = os.path.join(
        PEAKS_DIR, re.sub(r"[^A-Za-z0-9_.-]", "_", device_kind) + ".json")
    if not os.path.exists(path):
        raise KeyError(f"no published peaks for device kind {device_kind!r}: "
                       f"add {os.path.relpath(path)} with its source")
    with open(path) as f:
        peaks = json.load(f)
    if peaks["device_kind"] != device_kind:
        raise KeyError(f"{path} is for {peaks['device_kind']!r}, "
                       f"not {device_kind!r}")
    return peaks
