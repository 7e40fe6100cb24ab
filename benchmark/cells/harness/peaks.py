"""Published peaks of the chips the benchmark may run on, by ``device_kind``.

A device that is not in the table is an error, never a default: a share of a
peak against the wrong peak is a wrong number.
"""

_V5E = {
    "flops_per_s": 197e12,        # bf16 matrix units
    "hbm_bytes_per_s": 819e9,
    "hbm_bytes": 16e9,
    "source": "Google Cloud documentation, 'TPU v5e' system architecture",
}

PEAKS = {
    "TPU v5 lite": _V5E,          # what jax reports on a v5e
    "TPU v5e": _V5E,
}


class UnknownDevice(Exception):
    pass


def peaks_for(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            "device_kind %r is not in the peaks table (%s): add it with its "
            "source before measuring on it" % (device_kind, sorted(PEAKS)))
