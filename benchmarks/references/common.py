"""What the plain references share: exact decimals from integers, and
the calendar of the generated table.  numpy and the standard library
only -- nothing of the engine."""

import datetime
import decimal

EPOCH = datetime.date(1970, 1, 1)
RETURNFLAGS = ("A", "N", "R")
LINESTATUSES = ("F", "O")
N_DISC = 11
N_TAX = 9


def days(d: datetime.date) -> int:
    return (d - EPOCH).days


def date_of(day: int) -> datetime.date:
    return EPOCH + datetime.timedelta(days=int(day))


#: first l_shipdate the table can hold (1992-01-02): day 0 of the
#: statistics' ship-day axis
SHIP_LO = days(datetime.date(1992, 1, 2))


def dec(scaled: int, scale: int) -> decimal.Decimal:
    return decimal.Decimal(int(scaled)).scaleb(-scale)


def avg_dec(total: int, n: int, scale: int) -> decimal.Decimal:
    """SQL avg of a decimal(..., scale): the exact quotient at scale + 6,
    rounded half up -- in integers, so no float is involved."""
    q, r = divmod(int(total) * 10 ** 6, int(n))
    if 2 * r >= n:
        q += 1
    return dec(q, scale + 6)


def exact_sum(a) -> int:
    """Sum of an int64 array in Python integers: the full-table sum of
    charges comes within a small factor of int64's range."""
    return sum(int(x) for x in a.ravel())
