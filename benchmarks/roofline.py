"""The bytes a scan has to read, from the query's own file, and the
least time the chip could take for them.  Kept with the benchmark so no
later change to the program moves the yardstick."""

from .peaks import peak

#: bytes a value occupies in the engine's SQL types on the device:
#: decimals and bigints are scaled int64, dates and dictionary codes of
#: text are int32 (bench.py's Q1_BYTES_PER_ROW arithmetic)
TYPE_BYTES = {"decimal": 8, "bigint": 8, "date": 4, "text": 4}
VALIDITY_BYTES = 1      # one validity byte per column


def algorithmic_bytes_per_row(query) -> int:
    """Unpadded bytes per table row the query's columns occupy."""
    return sum(TYPE_BYTES[t] + VALIDITY_BYTES
               for t in query["scanned_columns"].values())


def hbm_floor_s(n_bytes: float, device_kind: str, chips: int) -> float:
    """Least seconds ``chips`` chips of this kind need to read ``n_bytes``."""
    return n_bytes / (peak(device_kind, "hbm_bytes_per_s") * chips)
