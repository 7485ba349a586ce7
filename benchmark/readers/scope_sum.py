"""Device self time per iteration of the traced window, in milliseconds,
of the operations under any of several ``jax.named_scope``s (``scopes``):
the sum of ``scope_time``'s reading of each.  The scopes listed do not
nest in one another, so no event is counted twice."""
from benchmark.readers import scope_time


def read(reading: dict, spec: dict):
    each = [scope_time.read(reading, {**spec, "scope": s})
            for s in spec["scopes"]]
    return None if any(v is None for v in each) else sum(each)
