"""Published peaks of the chips the benchmark may run on, keyed by the
exact ``device_kind`` JAX reports.  A kind that is not here is an
error, never a default."""

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 16 GB HBM2e at 819 GB/s,
    # 197 TFLOP/s bf16, 393 TOP/s int8 per chip
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flop_per_s": 197e12,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peak(device_kind: str, what: str) -> float:
    try:
        return PEAKS[device_kind][what]
    except KeyError:
        raise KeyError(
            f"no {what} on record for device kind {device_kind!r}; add it "
            f"to benchmarks/peaks.py with its source") from None
