"""Published per-chip peaks, keyed by ``device_kind``.  An unknown kind is
an error, never a default."""

from __future__ import annotations

import json
from pathlib import Path

TABLE = json.loads((Path(__file__).with_name("peaks.json")).read_text())


def peaks(device_kind: str) -> dict:
    try:
        return TABLE["kinds"][device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(TABLE['kinds'])}"
        ) from None
