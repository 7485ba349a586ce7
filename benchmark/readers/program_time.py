"""The ``programs`` table of the program's telemetry (``obs.summary()``):
what JAX traced, lowered and compiled, by program name.

``{"program": <name>}`` reads one row, ``{"all_but": [<name>, ...]}`` all
rows but the named ones; ``fields`` (``count``, ``trace_s``, ``lower_s``,
``backend_s``) are summed.  ``{"between": [<mark>, <mark>]}`` reads what
was added between two of the harness's marks, ``{"since": <mark>}`` what
was added from a mark to now (the program's live summary: the window,
and whatever the process compiled after it).  A program that keeps no
such table reads nothing; a table without the row reads 0.
"""


def total(summary: dict, spec: dict):
    table = summary.get("programs")
    if table is None:
        return None
    if "program" in spec:
        rows = [table.get(spec["program"], {})]
    else:
        rows = [r for name, r in table.items() if name not in spec["all_but"]]
    return sum(r.get(f, 0) for r in rows for f in spec["fields"])


def read(reading: dict, spec: dict):
    if "since" in spec:
        from lightgbm_tpu import obs
        a, b = reading["obs"][spec["since"]], obs.summary()
    else:
        a, b = (reading["obs"][k] for k in spec["between"])
    a, b = total(a, spec), total(b, spec)
    return None if a is None or b is None else float(b - a)
