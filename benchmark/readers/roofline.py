"""Share of a roofline: the least time the chip could take for the
named work of the traced window (``work.py``), over the time it took:
one class of operations' device time, or the whole window."""
from benchmark import work


def read(reading: dict, spec: dict):
    red = reading.get("trace")
    if red is None:
        return None
    took = (red["window_s"] if spec["time"] == "window"
            else red["class_s"].get(spec["time"]))
    if not took:
        return None
    least, _bound = work.least_time(reading["work"][spec["work"]],
                                    reading["device"]["kind"])
    return 100.0 * least / took
