"""The multiply-adds the factored one-hot group product has to make, and
the least time the chip's MXU could take for them.  Kept with the
benchmark, beside ``roofline.py``, so no later change to the program
moves the yardstick.

The product sums ``planes`` small-integer planes (a 0/1 plane a count,
eight 8-bit limbs an int64 sum; the query's own file counts them) of
every table row into one of ``slots`` group slots: a one-hot row of
``slots`` entries against ``planes`` values is ``slots x planes``
multiply-adds a row, two operations each, however the one-hot is
factored.  Padding rows and the slots a power-of-two split adds are the
program's own cost and are not counted."""

from .peaks import peak


def product_flops(slots: float, planes: int, rows: int) -> float:
    return 2.0 * slots * planes * rows


def mxu_floor_s(flops: float, device_kind: str, chips: int) -> float:
    """Least seconds ``chips`` chips of this kind need for ``flops``
    bfloat16 operations."""
    return flops / (peak(device_kind, "bf16_flop_per_s") * chips)
