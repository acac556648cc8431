"""Published peaks of the chips the benchmark runs on, keyed by ``device_kind``.

A device that is not in this table is an error: the benchmark never falls
back to a guess.
"""

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16 * 2**30,
        "source": "Google Cloud documentation, 'TPU v5e' (cloud.google.com/tpu/docs/v5e)",
    },
}


def peaks_for(device_kind: str) -> dict:
    """The peak row of ``device_kind``; raises KeyError for any other chip."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device_kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
