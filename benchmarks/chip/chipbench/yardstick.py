"""Byte counts and peaks: the fixed arithmetic behind the roofline shares."""

from __future__ import annotations

import json
from pathlib import Path

HERE = Path(__file__).resolve().parent.parent
WORD = 4  # bytes per RNS residue (uint32)


def ks_min_bytes(n: int, level: int, alpha: int, reads_input: bool = True) -> int:
    """HBM bytes that any implementation of one hybrid key-switch at ``level``
    must move: read the input d2 ((l+1) limbs), write both outputs
    (2(l+1) limbs), and read ONE half of the beta active key digits over the
    extended basis (beta(l+1+alpha) limbs).  The uniform half of each key can be
    regenerated on chip from a seed, so it is not counted.  A rotation hoisted
    with others of the same ciphertext shares their input read
    (``reads_input=False``)."""
    limbs = level + 1
    beta = -(-limbs // alpha)
    return ((limbs if reads_input else 0) + 2 * limbs + beta * (limbs + alpha)) * n * WORD


def peaks(device_kind: str, path: Path = HERE / "peaks.json") -> dict:
    """The peak table row for ``device_kind``; a kind not in the table is an error."""
    table = json.loads(Path(path).read_text())
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"device kind {device_kind!r} is not in {path.name}; add its published peaks")
    return dict(table[device_kind], source=table["source"])
