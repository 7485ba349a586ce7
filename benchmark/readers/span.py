"""A span of the program's telemetry (``obs.summary()``): its total
seconds or its count between two of the harness's marks."""


def read(reading: dict, spec: dict):
    a, b = (reading["obs"][k] for k in spec["between"])
    get = lambda s: s["spans"].get(spec["span"], {}).get(spec["field"], 0)  # noqa: E731
    v = get(b) - get(a)
    return float(v) if v else None
