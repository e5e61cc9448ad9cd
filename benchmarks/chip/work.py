"""Operations and bytes of the layers a model module lists
(`models/<name>.py`, `layers(geom)`), from a configuration's geometry
alone (the benchmark's own config file, not the program).

An int8 multiply-accumulate is one MAC and two operations.  Bytes are
the int8 operands the algorithm has to move through HBM: activations in
and out per image, weights once per wave.  Each layer entry has a
`name`, a `kind`, `macs` and `act_bytes` per image and `weight_bytes`
per wave.
"""
from __future__ import annotations


def macs_per_image(layers: list, kinds=None) -> int:
    return sum(l["macs"] for l in layers
               if kinds is None or l["kind"] in kinds)


def work(layers: list, kinds, rows: int, waves: int) -> tuple:
    """(operations, bytes) of the layers of `kinds` over `waves` waves
    that compute `rows` rows in all (padding rows included: the device
    computes them)."""
    ops = nbytes = 0
    for l in layers:
        if l["kind"] in kinds:
            ops += 2 * l["macs"] * rows
            nbytes += l["act_bytes"] * rows + l["weight_bytes"] * waves
    return ops, nbytes
