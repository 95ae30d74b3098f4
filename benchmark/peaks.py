"""Published per-chip peaks, keyed by jax's `device_kind`.

Copied from bench.DEVICE_PEAKS (the original is listed in PERF.md's
Open questions). A device that is not here is an error, never a default:
a share of a peak that nobody published is not a number.
"""
DEVICE_PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": 'Google Cloud documentation, "TPU v5e"',
    },
}


def device_peaks(device_kind):
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peak for device_kind {device_kind!r} "
            f"(known: {sorted(DEVICE_PEAKS)})") from None
