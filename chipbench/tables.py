"""The one traffic generator: host tables from a configuration, a traffic
mix and a seed.

A configuration (``configs/<name>.json``) fixes the schema: its ``tables``
list gives each table's columns and dtypes, and ``key`` names the join or
sort key.  A traffic mix (``traffic/<name>.json``) fixes how the keys are
drawn: its ``keys`` list gives one key distribution a table, the last one
repeating for tables beyond the list.  Payload columns are standard normal
floats.  The same seed gives the same tables, bit for bit.

Key distributions, each over ``[0, range * rows)``:

* ``{"dist": "uniform", "range": r}``
* ``{"dist": "zipf", "a": a, "range": r}``: a bounded power law, key ``i``
  drawn with weight about ``(i + 1) ** -a``, so small keys are hot.
"""
from __future__ import annotations

import numpy as np


def rng_for(seed: int, stream: int = 0) -> np.random.Generator:
    """A generator for any whole-number seed (large or negative ones too)."""
    return np.random.default_rng(
        np.random.SeedSequence([seed % 2**64, stream]))


def draw_keys(rng: np.random.Generator, spec: dict, rows: int,
              dtype) -> np.ndarray:
    high = max(1, int(round(spec.get("range", 1.0) * rows)))
    dist = spec.get("dist", "uniform")
    if dist == "uniform":
        return rng.integers(0, high, rows, dtype=dtype)
    if dist == "zipf":
        # inverse CDF of the continuous power law on [1, high + 1)
        a = float(spec["a"])
        u = rng.random(rows)
        if a == 1.0:
            x = np.exp(u * np.log(high + 1.0))
        else:
            x = (((high + 1.0) ** (1.0 - a) - 1.0) * u + 1.0) ** (1.0 / (1.0 - a))
        return np.minimum(np.floor(x) - 1.0, high - 1).astype(dtype)
    raise ValueError(f"unknown key distribution {dist!r}")


def make_tables(config: dict, traffic: dict, rows: int,
                seed: int) -> list[dict]:
    """One dict of numpy columns a table of ``config``, ``rows`` rows each."""
    rng = rng_for(seed)
    specs = traffic["keys"]
    key = config["key"]
    tables = []
    for i, schema in enumerate(config["tables"]):
        spec = specs[min(i, len(specs) - 1)]
        cols = {}
        for name, dtype in schema.items():
            dtype = np.dtype(dtype)
            if name == key:
                cols[name] = draw_keys(rng, spec, rows, dtype)
            else:
                cols[name] = rng.standard_normal(rows, dtype=dtype)
        tables.append(cols)
    return tables
