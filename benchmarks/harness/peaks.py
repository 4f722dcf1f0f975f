"""Published peaks of one chip, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16,
16 GB of HBM at 819 GB/s. A device that is not in the table is an
error, not a default (copied from ``bench.py`` ``HBM_PEAK_GBPS``)."""

PEAKS = {
    "TPU v5 lite": {"hbm_gbps": 819.0, "bf16_tflops": 197.0,
                    "source": 'Google Cloud documentation, "TPU v5e"'},
}


def peaks_for(device_kind):
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device_kind "
                       f"{device_kind!r}; add it to harness/peaks.py "
                       f"with its source")
    return PEAKS[device_kind]
