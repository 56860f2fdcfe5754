"""Parameter draws of a statement, from data.  A query file lists its
parameters; a traffic file picks the mode that fills them:

``fixed``        the query's ``fixed`` values (the validation parameters)
``tpch``         each parameter uniform over its own ``lo..hi`` / ``choices``
``uniform_key``  the key parameter uniform over the data set's sampled keys
``zipf``         the key parameter Zipf(theta) over the same keys, by rank

Derived parameters (``from`` + ``add``) and ``format`` strings turn the
draws into the text qgen would have substituted.
"""

import numpy as np

from .spec import SpecError


def _draw_one(spec, rng):
    if "choices" in spec:
        return spec["choices"][int(rng.integers(len(spec["choices"])))]
    return int(rng.integers(spec["lo"], spec["hi"] + 1))


class KeyPicker:
    """Keys of the data set by rank, uniform or Zipf(theta)."""

    def __init__(self, keys, theta=None):
        if len(keys) == 0:
            raise SpecError("the data set holds no sampled keys to look up")
        self.keys = keys
        self.cdf = None
        if theta is not None:
            w = 1.0 / np.arange(1, len(keys) + 1) ** float(theta)
            self.cdf = np.cumsum(w / w.sum())

    def draw(self, rng) -> int:
        if self.cdf is None:
            return int(self.keys[int(rng.integers(len(self.keys)))])
        rank = int(np.searchsorted(self.cdf, rng.random()))
        return int(self.keys[min(rank, len(self.keys) - 1)])


class Statement:
    """One query under one parameter mode: ``draw(rng)`` gives the raw
    parameters (what the reference takes), ``render(raw)`` the SQL text
    and bind list (what the engine takes)."""

    def __init__(self, name, query, mode, stats):
        self.name = name
        self.query = query
        self.specs = query.get("parameters", {})
        kind = mode if isinstance(mode, str) else mode["kind"]
        if kind not in ("fixed", "tpch", "uniform_key", "zipf"):
            raise SpecError(f"unknown parameter mode {kind!r}")
        self.kind = kind
        self.picker = None
        if kind in ("uniform_key", "zipf"):
            theta = mode["theta"] if kind == "zipf" else None
            self.picker = KeyPicker(
                np.unique(stats[query["key_parameter"]["keys"]]), theta)

    def draw(self, rng) -> dict:
        if self.kind == "fixed":
            return {k: s["fixed"] for k, s in self.specs.items()
                    if "fixed" in s}
        raw = {k: _draw_one(s, rng) for k, s in self.specs.items()
               if "lo" in s or "choices" in s}
        if self.picker is not None:
            raw[self.query["key_parameter"]["name"]] = self.picker.draw(rng)
        return raw

    def render(self, raw: dict):
        values = dict(raw)
        for k, s in self.specs.items():
            if "from" in s:
                values[k] = values[s["from"]] + s.get("add", 0)
        text = {k: self.specs.get(k, {}).get("format", "{}").format(v)
                for k, v in values.items()}
        sql = self.query["sql"].format(**text)
        bind = [values[k] for k in self.query.get("bind", [])] or None
        return sql, bind
