"""The bytes an ORDER BY ... LIMIT over an aggregate has to read before
it can cut, and the least time the chip could take for them.  Kept with
the benchmark, beside ``roofline.py``, so no later change to the program
moves the yardstick.

Every group that stands before the cut has to be read once, whatever
implements the cut: the key that decides the group, the partial states
of the aggregates and the group's row count, as the query's own file
lists them under ``group_state`` (type by type at the widths of
``roofline.TYPE_BYTES``; no validity byte: a state is a number).  The
slots of a table that hold no group, the lanes a program carries beside
those and what it sorts or gathers are the program's own cost and are
not counted."""

from .roofline import TYPE_BYTES, hbm_floor_s


def group_bytes(query) -> int:
    """Bytes of one group of the query before the cut."""
    return sum(TYPE_BYTES[t] for t in query["group_state"]["columns"].values())


def cut_floor_s(groups: float, query, device_kind: str, chips: int) -> float:
    """Least seconds ``chips`` chips of this kind need to read ``groups``
    groups of ``query`` once."""
    return hbm_floor_s(groups * group_bytes(query), device_kind, chips)
