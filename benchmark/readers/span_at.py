"""A span of the program's telemetry (``obs.summary()``) as it stood at
one of the harness's marks: its total seconds or its count.  At
``start`` (after ``make_dataset``, before ``lgb.train``) the summary
holds ingest alone.  A span the program does not have reads nothing."""


def read(reading: dict, spec: dict):
    got = reading["obs"][spec["at"]]["spans"].get(spec["span"])
    return None if got is None else float(got[spec["field"]])
