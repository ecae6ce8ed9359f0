"""Published peaks by ``device_kind``. An unknown device is an error, never a
default. No roofline share is reported yet (PERF.md §3: the programs are
32-bit integer limb arithmetic on the VPU and no int32 peak is published);
the table is here so the metric that follows reads one source."""

PEAKS = {
    "TPU v5 lite": {
        "source": "Google Cloud documentation, 'TPU v5e' system architecture",
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise SystemExit(
            f"benchmark/peaks.py has no entry for device_kind {device_kind!r}"
        ) from None
