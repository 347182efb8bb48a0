"""Peak HBM bandwidth of one chip, bytes per second, by `device_kind`.

Source: Google Cloud TPU documentation, the system-architecture page of
each generation ("TPU v5e": 16 GB HBM2e at 819 GB/s; "TPU v5p": 2,765 GB/s;
"TPU v4": 1,228 GB/s; "TPU v6e": 1,640 GB/s). Copied from `bench.py`'s
`HBM_PEAK` (PR 21), which a later PR may delete. A device that is not in
the table is an error, never a default.
"""

HBM_BYTES_PER_S = {
    "TPU v5 lite": 819e9,       # v5e
    "TPU v5e": 819e9,
    "TPU v5p": 2765e9,
    "TPU v4": 1228e9,
    "TPU v6 lite": 1640e9,      # v6e
    "TPU v6e": 1640e9,
}


def hbm_bytes_per_s(device_kind):
    try:
        return HBM_BYTES_PER_S[device_kind]
    except KeyError:
        raise LookupError(
            f"no HBM peak on record for device_kind {device_kind!r}: add "
            f"it to benchmark/harness/peaks.py with its source") from None
