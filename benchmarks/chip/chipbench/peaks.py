"""The chip's published peaks, keyed by JAX's ``device_kind``
(``peaks.json`` beside the harness holds them with their source).  A kind
that is not in the table is an error, never a default."""

from __future__ import annotations

import dataclasses
import json
import pathlib

TABLE = pathlib.Path(__file__).resolve().parents[1] / "peaks.json"


@dataclasses.dataclass(frozen=True)
class Peaks:
    kind: str
    flops: float          # bf16 FLOP/s
    hbm_bw: float         # HBM bytes/s
    hbm_bytes: float      # HBM capacity
    source: str


def lookup(kind: str, table: pathlib.Path = TABLE) -> Peaks:
    doc = json.loads(table.read_text())
    if kind not in doc["devices"]:
        raise KeyError(f"device kind {kind!r} has no peaks in {table.name}; "
                       f"known: {sorted(doc['devices'])}")
    d = doc["devices"][kind]
    return Peaks(kind, float(d["bf16_flops_per_s"]),
                 float(d["hbm_bytes_per_s"]), float(d["hbm_bytes"]),
                 doc["source"])
