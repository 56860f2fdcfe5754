"""The one general load generator: it reads a traffic mix (a data file)
and drives ``cl.execute`` from the client's side.

``loop: closed``  ``clients`` callers, each sending its next statement
                  when the last came back.
``loop: open``    statements due at a fixed ``rate_per_s`` whatever the
                  system does; each is timed from when it was due, and
                  how late the generator sent it is reported.

A mix lists ``statements`` (query, parameter mode, weight).  With
``ordering: cycle`` a client goes through them in the order listed
(each ``weight`` times) and the window closes only at the end of a
cycle, so the kinds stay balanced; with ``ordering: random`` each
statement is a weighted draw.  The window measures for ``seconds``: no
cycle starts after that, and the statements in flight are completed and
counted with the time they took.
"""

import contextlib
import threading
import time

import numpy as np

from .params import Statement
from .spec import SpecError


class Record:
    __slots__ = ("name", "raw", "due", "sent", "done", "rows", "pipeline",
                 "error")

    def __init__(self, name, raw, due):
        self.name, self.raw, self.due = name, raw, due
        self.sent = self.done = None
        self.rows = self.pipeline = self.error = None

    @property
    def latency_s(self):
        return self.done - self.due


def build_statements(traffic, queries, stats):
    return [(Statement(s["query"], queries[s["query"]],
                       s.get("parameters", "fixed"), stats),
             int(s.get("weight", 1))) for s in traffic["statements"]]


def cycles(statements, traffic, rng):
    """Endless stream of cycles; a cycle is a list of (statement, raw)."""
    ordering = traffic.get("ordering", "cycle")
    if ordering == "cycle":
        order = [st for st, w in statements for _ in range(w)]
        while True:
            yield [(st, st.draw(rng)) for st in order]
    elif ordering == "random":
        p = np.array([w for _, w in statements], float)
        p /= p.sum()
        while True:
            st = statements[int(rng.choice(len(statements), p=p))][0]
            yield [(st, st.draw(rng))]
    else:
        raise SpecError(f"unknown ordering {ordering!r}")


def execute(cl, rec, st, annotate):
    """One statement to rows on the host; an exception is a failed
    operation, recorded and not raised."""
    sql, bind = st.render(rec.raw)
    span = contextlib.nullcontext()
    if annotate:
        import jax
        span = jax.profiler.TraceAnnotation("bench.execute." + st.name)
    rec.sent = time.perf_counter()
    if rec.due is None:
        rec.due = rec.sent
    try:
        with span:
            r = cl.execute(sql, params=bind)
        rec.rows = r.rows
        rec.pipeline = dict((r.explain or {}).get("pipeline") or {})
    except Exception as e:  # noqa: BLE001 - the boundary that counts failures
        rec.error = f"{type(e).__name__}: {e}"
    rec.done = time.perf_counter()


def run_cycles(cl, statements, traffic, seed, n_cycles):
    """``n_cycles`` cycles on the calling thread (warm-up): every
    statement of the mix at least once, from a stream of draws the
    window does not use."""
    rng = np.random.default_rng([seed, 0xC01D])
    stream = cycles(statements, traffic, rng)
    records = []
    for _ in range(n_cycles):
        for st, raw in next(stream):
            rec = Record(st.name, raw, None)
            execute(cl, rec, st, False)
            records.append(rec)
    return records


def run_window(cl, statements, traffic, seed, seconds, annotate=False,
               on_cycle=None):
    """-> (records, t_start).  ``on_cycle(n)`` is called after the n-th
    completed cycle (the traced run stops the profiler from it)."""
    loop = traffic.get("loop", "closed")
    clients = int(traffic.get("clients", 1))
    records, lock = [], threading.Lock()
    done_cycles = [0]
    t_start = time.perf_counter()

    def finished(cycle_records):
        with lock:
            records.extend(cycle_records)
            done_cycles[0] += 1
            n = done_cycles[0]
        if on_cycle is not None:
            on_cycle(n)

    if loop == "closed":
        def client(i):
            stream = cycles(statements, traffic,
                            np.random.default_rng([seed, i]))
            while time.perf_counter() - t_start < seconds:
                out = []
                for st, raw in next(stream):
                    rec = Record(st.name, raw, None)
                    execute(cl, rec, st, annotate)
                    out.append(rec)
                finished(out)
    elif loop == "open":
        period = 1.0 / float(traffic["rate_per_s"])
        stream = cycles(statements, traffic, np.random.default_rng([seed, 0]))
        ticket = [0]

        def client(i):
            while True:
                with lock:
                    n = ticket[0]
                    if n * period >= seconds:
                        return
                    ticket[0] += 1
                    cycle = next(stream)
                due = t_start + n * period
                wait = due - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                out = []
                for st, raw in cycle:
                    rec = Record(st.name, raw, due)
                    execute(cl, rec, st, annotate)
                    out.append(rec)
                    due = None      # the cycle's later statements follow on
                finished(out)
    else:
        raise SpecError(f"unknown loop kind {loop!r}")

    if clients == 1:
        client(0)
    else:
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    return records, t_start
