"""Device self time per iteration of the traced window, in
milliseconds: of one class of operations (``class``), or of everything
but some classes (``except``)."""


def read(reading: dict, spec: dict):
    red = reading.get("trace")
    if red is None:
        return None
    if "class" in spec:
        s = red["class_s"].get(spec["class"])
    else:
        s = red["total_self_s"] - sum(red["class_s"].get(c, 0.0)
                                      for c in spec["except"])
    if not s:
        return None
    return 1000.0 * s / reading["iterations"]
