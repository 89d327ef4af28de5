"""Published peaks of the chips the benchmark knows, keyed by `device_kind`
as jax reports it.  A kind that is not here is an error, never a default."""

#: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8,
#: 16 GB of HBM at 819 GB/s, per chip
PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peaks(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind={device_kind!r}; known: "
            f"{sorted(PEAKS)} (add the chip with its source)") from None
