"""The end-to-end metrics, from the client's side, and the comparison
that decides ``correct``.  Latency is host clock from the call of
``cl.execute`` (or, in an open loop, from when the statement was due)
to rows on the host."""

import numpy as np

from .spec import plugin


def _latencies_ms(records):
    return np.array([r.latency_s for r in records]) * 1e3


def query_p50_ms(records, t_start, table_rows):
    return float(np.percentile(_latencies_ms(records), 50))


def query_p95_ms(records, t_start, table_rows):
    return float(np.percentile(_latencies_ms(records), 95))


def scan_rows_per_s(records, t_start, table_rows):
    """Table rows x queries completed / the time those queries took."""
    return table_rows * len(records) / (max(r.done for r in records) - t_start)


END_TO_END = {"query_p50_ms": query_p50_ms, "query_p95_ms": query_p95_ms,
              "scan_rows_per_s": scan_rows_per_s}


def end_to_end(names, records, t_start, table_rows):
    """-> {name: value} for the completed, unfailed ``records``."""
    done = [r for r in records if r.error is None]
    return {n: END_TO_END[n](done, t_start, table_rows)
            for n in names if n in END_TO_END and done}


class Checker:
    """Holds every answer to the plain reference.  The reference is
    asked once per distinct (query, parameters); every answer is
    compared, exactly."""

    def __init__(self, queries, stats):
        self.queries, self.stats = queries, stats
        self._expected = {}
        self.wrong = []

    def expected(self, name, raw):
        key = (name, tuple(sorted(raw.items())))
        if key not in self._expected:
            ref = plugin("references", self.queries[name]["reference"])
            rows = [tuple(r) for r in ref.expected(self.stats, raw)]
            if not self.queries[name].get("ordered"):
                rows.sort()
            self._expected[key] = rows
        return self._expected[key]

    def check(self, records) -> bool:
        """True when every completed record's rows equal the reference's."""
        for r in records:
            if r.error is not None:
                continue
            got = [tuple(row) for row in r.rows]
            if not self.queries[r.name].get("ordered"):
                got.sort()
            want = self.expected(r.name, r.raw)
            if got != want:
                self.wrong.append({"query": r.name, "parameters": r.raw,
                                   "got": repr(got[:3]), "want": repr(want[:3]),
                                   "rows": [len(got), len(want)]})
        return not self.wrong
