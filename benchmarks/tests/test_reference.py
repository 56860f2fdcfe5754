"""The generator's shapes, and the sufficient-statistics references
against a brute-force numpy evaluation over the generated rows."""

import datetime
import decimal

import numpy as np
import pytest

from benchmarks.generators import tpch_lineitem as gen
from benchmarks.references import (groupby_ship_disc_tax, groupby_shipdate,
                                   orderkey_lookup, q1, q6)
from benchmarks.references.common import avg_dec, days, dec

PARAMS = {"orders": 30_000, "parts": 2_000_000, "chunk_orders": 8_000,
          "lookup_sample_orders": 64}


@pytest.fixture(scope="module")
def table():
    stats = gen.Statistics(PARAMS)
    chunks = []
    for i in range(gen.n_chunks(PARAMS)):
        c = gen.generate_chunk(PARAMS, 5, i)
        stats.add(c)
        chunks.append(c)
    rows = {k: np.concatenate([c[k] for c in chunks])
            for k in chunks[0] if k != "lines_per_order"}
    return rows, stats.arrays()


def test_generator_shapes(table):
    rows, stats = table
    n = rows["qty"].size
    assert int(stats["rows"]) == n
    assert gen.SHIP_DAYS == 2526 and gen.SHIP_LO == gen.START_DATE + 1
    # 1..7 lines per order, each count about a seventh of the orders
    hist = stats["lines_hist"]
    assert hist[0] == 0 and hist[1:].sum() == PARAMS["orders"]
    assert (hist[1:] > PARAMS["orders"] / 7 * 0.9).all()
    assert set(np.unique(rows["qty"])) == {q * 100 for q in range(1, 51)}
    assert rows["disc"].min() == 0 and rows["disc"].max() == 10
    assert rows["tax"].min() == 0 and rows["tax"].max() == 8
    assert rows["price"].min() >= 90_000 and rows["price"].max() <= 10_495_000
    assert (rows["okey"] % 32 >= 1).all() and (rows["okey"] % 32 <= 8).all()
    # published Q1 has its four groups: A/F, N/F, N/O, R/F
    groups = {(int(f), int(s)) for f, s in zip(rows["rf"], rows["ls"])}
    assert groups == {(0, 0), (1, 0), (1, 1), (2, 0)}
    # published Q6 keeps 1 to 3 % of the rows
    published = {"YEAR": 1994, "DISCOUNT": 6, "QUANTITY": 24}
    assert 0.01 < q6.matching_rows(stats, published) / n < 0.03


def test_same_seed_same_table_other_seed_other_table():
    a = gen.generate_chunk(PARAMS, 5, 1)
    b = gen.generate_chunk(PARAMS, 5, 1)
    c = gen.generate_chunk(PARAMS, 6, 1)
    assert all((a[k] == b[k]).all() for k in a)
    assert a["qty"].size != c["qty"].size or (a["qty"] != c["qty"]).any()


def brute_q1(rows, delta):
    cutoff = days(datetime.date(1998, 12, 1)) - delta
    out = []
    for f, flag in enumerate("ANR"):
        for s, status in enumerate("FO"):
            m = (rows["ship"] <= cutoff) & (rows["rf"] == f) & (rows["ls"] == s)
            n = int(m.sum())
            if not n:
                continue
            qty, price, disc, tax = (rows[k][m].astype(object)
                                     for k in ("qty", "price", "disc", "tax"))
            dprice = price * (100 - disc)
            out.append((flag, status, dec(qty.sum(), 2), dec(price.sum(), 2),
                        dec(dprice.sum(), 4), dec((dprice * (100 + tax)).sum(), 6),
                        avg_dec(qty.sum(), n, 2), avg_dec(price.sum(), n, 2),
                        avg_dec(disc.sum(), n, 2), n))
    return out


def brute_q6(rows, year, disc, qty):
    m = ((rows["ship"] >= days(datetime.date(year, 1, 1)))
         & (rows["ship"] < days(datetime.date(year + 1, 1, 1)))
         & (rows["disc"] >= disc - 1) & (rows["disc"] <= disc + 1)
         & (rows["qty"] < qty * 100))
    if not m.any():
        return [(None,)]
    return [(dec((rows["price"][m].astype(object) * rows["disc"][m]).sum(), 4),)]


@pytest.mark.parametrize("draw", range(6))
def test_q1_reference_equals_brute_force(table, draw):
    rows, stats = table
    delta = int(np.random.default_rng(draw).integers(60, 121))
    assert q1.expected(stats, {"DELTA": delta}) == brute_q1(rows, delta)


def test_q1_reference_at_the_edges(table):
    rows, stats = table
    for delta in (0, 90, 2525, 2526, 4000):
        assert q1.expected(stats, {"DELTA": delta}) == brute_q1(rows, delta)


@pytest.mark.parametrize("draw", range(8))
def test_q6_reference_equals_brute_force(table, draw):
    rows, stats = table
    rng = np.random.default_rng(100 + draw)
    year, disc = int(rng.integers(1993, 1998)), int(rng.integers(2, 10))
    qty = (24, 25)[draw % 2]
    want = brute_q6(rows, year, disc, qty)
    got = q6.expected(stats, {"YEAR": year, "DISCOUNT": disc, "QUANTITY": qty})
    assert got == want and got != [(None,)]


def test_q6_reference_refuses_a_quantity_it_cannot_answer(table):
    with pytest.raises(ValueError):
        q6.expected(table[1], {"YEAR": 1994, "DISCOUNT": 6, "QUANTITY": 30})


def test_groupby_references_equal_brute_force(table):
    rows, stats = table
    by_day, by_ddt = {}, {}
    for ship, disc, tax, qty, price in zip(rows["ship"], rows["disc"],
                                           rows["tax"], rows["qty"],
                                           rows["price"]):
        d = datetime.date(1970, 1, 1) + datetime.timedelta(days=int(ship))
        n, q, mx = by_day.get(d, (0, 0, 0))
        by_day[d] = (n + 1, q + int(qty), max(mx, int(price)))
        k = (d, dec(disc, 2), dec(tax, 2))
        n, q = by_ddt.get(k, (0, 0))
        by_ddt[k] = (n + 1, q + int(qty))
    assert groupby_shipdate.expected(stats, {}) == sorted(
        (d, n, dec(q, 2), dec(mx, 2)) for d, (n, q, mx) in by_day.items())
    assert groupby_ship_disc_tax.expected(stats, {}) == sorted(
        k + (n, dec(q, 2)) for k, (n, q) in by_ddt.items())


def test_lookup_reference_returns_the_orders_rows(table):
    rows, stats = table
    keys = np.unique(stats["lookup_okey"])
    assert 64 <= len(keys) <= 65
    for key in keys[:5]:
        m = rows["okey"] == key
        want = sorted(
            (int(key), dec(q, 2), dec(p, 2),
             datetime.date(1970, 1, 1) + datetime.timedelta(days=int(s)),
             "ANR"[int(f)])
            for q, p, s, f in zip(rows["qty"][m], rows["price"][m],
                                  rows["ship"][m], rows["rf"][m]))
        assert 1 <= len(want) <= 7
        assert sorted(orderkey_lookup.expected(stats, {"KEY": int(key)})) == want


def test_avg_rounds_half_up_in_integers():
    assert avg_dec(1, 2, 2) == decimal.Decimal("0.00500000")
    assert avg_dec(1, 3, 0) == decimal.Decimal("0.333333")
    assert avg_dec(2, 3, 0) == decimal.Decimal("0.666667")
