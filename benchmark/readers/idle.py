"""The device's idle share of the traced window."""


def read(reading: dict, spec: dict):
    red = reading.get("trace")
    if red is None or not red["window_s"]:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
