"""The table of published peaks, keyed by ``device_kind``. A device
that is not in ``peaks.json`` is an error, never a default."""

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def load(path=None):
    with open(path or os.path.join(_HERE, "peaks.json")) as f:
        return json.load(f)["peaks"]


def peak(device_kind, name, table=None):
    table = load() if table is None else table
    try:
        return float(table[device_kind][name])
    except KeyError:
        raise ValueError(
            f"no published {name!r} peak for device_kind {device_kind!r}: "
            "add it to chipbench/peaks.json with its source instead of "
            "borrowing another chip's") from None
