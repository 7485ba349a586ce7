"""A harness clock: seconds spent inside ``ctx.clock(<name>)``."""


def read(reading: dict, spec: dict):
    return reading["clocks"].get(spec["clock"])
