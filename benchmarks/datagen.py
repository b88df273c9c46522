"""Helpers the configurations' seeded generators share: every generator
draws from ``numpy.random.default_rng(seed)`` alone and writes parquet with
pyarrow's default encodings, so the same seed gives the same file."""
from __future__ import annotations

import os

import numpy as np


def plant_domain(col: np.ndarray, domain: np.ndarray,
                 rng: np.random.Generator, row_group: int) -> None:
    """Put every value of ``domain`` once into every row group of ``col``,
    at positions drawn from ``rng`` (in place). The engine's decode programs
    are keyed by each row group's dictionary size, so a value that one seed
    happens to miss in one row group would make that seed compile programs
    of its own; with the whole domain present every seed gives the same
    sizes. A row group shorter than the domain (rehearsal sizes) is left as
    drawn."""
    n = col.shape[0]
    for start in range(0, n, row_group):
        m = min(row_group, n - start)
        if domain.shape[0] > m:
            continue
        pos = rng.choice(m, size=domain.shape[0], replace=False)
        col[start + pos] = domain


def filler_columns(specs: list, seed: int, rows: int) -> dict:
    """The columns of the source's record that no query of the benchmark
    reads (``other_columns`` of a configuration), as cheap seeded values of
    the source's types, so that a file of the full record width can be
    written and the scan's column pruning is real. Drawn from a generator
    of their own: the columns the queries read are the same with or
    without them."""
    import pyarrow as pa

    rng = np.random.default_rng((seed, 1))
    out = {}
    for spec in specs:
        kind, n = spec["type"], int(spec["distinct"])
        draw = rng.integers(0, n, rows, dtype=np.int32)
        if kind == "string":
            values = [f"{spec['name']}-{i:0{int(spec['width_bytes'])}d}"
                      [-int(spec["width_bytes"]):] for i in range(n)]
            out[spec["name"]] = pa.DictionaryArray.from_arrays(
                pa.array(draw), pa.array(values, pa.string())
            ).cast(pa.string())
        elif kind == "float64":
            out[spec["name"]] = pa.array(draw / 100.0)
        elif kind == "date32":
            out[spec["name"]] = pa.array(draw + 8036, pa.date32())
        else:
            out[spec["name"]] = pa.array(draw.astype(kind) + 1)
    return out


def write_parquet(table, out_dir: str, name: str, row_group: int) -> str:
    import pyarrow.parquet as pq

    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, name)
    pq.write_table(table, path, row_group_size=row_group)
    return path

