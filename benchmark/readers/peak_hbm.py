"""Peak device memory of the fullest chip, in GiB."""


def read(reading: dict, spec: dict):
    return reading["device"]["memory_peak_bytes"] / 2.0 ** 30
