"""A span's self seconds (its time less its child spans') as they stood
at one of the harness's marks.  ``span_at`` takes a field every span
has; this one reads nothing from a program whose spans carry no self
time, or that has no such span."""


def read(reading: dict, spec: dict):
    got = reading["obs"][spec["at"]]["spans"].get(spec["span"], {})
    return float(got["self_s"]) if "self_s" in got else None
