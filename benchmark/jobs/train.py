"""Job kind ``train``: plain training on one resident dataset.

Set-up builds ONE booster through the entry a user calls
(``lgb.train(..., num_boost_round=block_iters,
keep_training_booster=True)``, which uploads, compiles the block
program and runs the first step), drives it through the steps the
reference follows by the window's own call (``GBDT.train(block_iters)``
then ``jax.block_until_ready(scores)``), reading the loss after each,
and hands that same booster to the window.  After the window: peak
memory is read, the program's state is dropped, and the plain reference
follows the first trees over the same rows (``check.py``).  A workload
may say ``"compile_cache": false``: what the training call of set-up
compiles is then kept out of JAX's persistent cache, so that set-up
compiles the same programs whether or not the seed has run before (the
block program closes over the labels, so its key is the seed's).
"""
from __future__ import annotations

import contextlib
import gc
import time

import numpy as np

FOLLOWED_STEPS = 3
TRACED_STEPS = 2


def program_params(cfg: dict) -> dict:
    """The configuration's parameters as the program takes them, its
    stated precision included."""
    return {**cfg["params"], "hist_mode": cfg["precision"]["hist_mode"]}


def make_dataset(ctx, lgb):
    from benchmark import data
    with ctx.clock("data"):
        X, y = data.make(ctx.cfg["data"], ctx.seed)
    with ctx.clock("ingest.bin"):
        ds = lgb.Dataset(X, label=y,
                         params={"max_bin": ctx.cfg["params"]["max_bin"]})
        ds.construct()
    return X, y, ds


def _loss_parts(score, y):
    import jax
    import jax.numpy as jnp
    per_row = jax.nn.softplus(score) - y * score
    pad = (-per_row.shape[0]) % 4096
    return jnp.sum(jnp.pad(per_row, (0, pad)).reshape(-1, 4096), axis=1)


@contextlib.contextmanager
def written_to_cache(jax, written: bool):
    """While open and not ``written``, no program that compiles is
    written into the persistent compile cache (one that takes under the
    least time is not, and the least is put out of reach); programs
    compiled before and after are."""
    name = "jax_persistent_cache_min_compile_time_secs"
    least = getattr(jax.config, name)
    if not written:
        jax.config.update(name, 1e9)
    try:
        yield
    finally:
        jax.config.update(name, least)


class Booster:
    """The timed object: one booster, its step and what it produced."""

    def __init__(self, ctx, lgb, ds, y, params: dict):
        import jax
        import jax.numpy as jnp
        self.jax = jax
        self.block_iters = int(ctx.cell["block_iters"])
        self.rows = len(y)
        self._loss = jax.jit(_loss_parts)
        self.y_dev = jnp.asarray(y)
        self.loss = []
        with ctx.clock("train_call"), \
                written_to_cache(jax, ctx.cell.get("compile_cache", True)):
            self.bst = lgb.train(params, ds, num_boost_round=self.block_iters,
                                 keep_training_booster=True)
            self.g = self.bst._gbdt
            jax.block_until_ready(self.g.scores)
        self.read_loss()

    def step(self) -> None:
        self.g.train(self.block_iters)
        self.jax.block_until_ready(self.g.scores)

    def followed_steps(self) -> None:
        """The rest of the steps the reference follows, by the window's
        own call, the loss read after each."""
        while len(self.loss) < FOLLOWED_STEPS:
            self.step()
            self.read_loss()

    def read_loss(self) -> None:
        parts = self._loss(self.g.scores[:, 0], self.y_dev)
        self.loss.append(float(np.sum(self.jax.device_get(parts),
                                      dtype=np.float64)) / self.rows)

    def outputs(self) -> dict:
        """What the followed steps produced, for the comparison."""
        n_trees = FOLLOWED_STEPS * self.block_iters
        trees = []
        for t in self.g.models[:n_trees]:
            L = int(t.num_leaves)
            trees.append({
                "num_leaves": L,
                "split_feature": np.array(t.split_feature[:L - 1]),
                "threshold": np.array(t.threshold[:L - 1]),
                "left_child": np.array(t.left_child[:L - 1]),
                "right_child": np.array(t.right_child[:L - 1]),
                "leaf_value": np.array(t.leaf_value[:L]),
                "leaf_count": np.array(t.leaf_count[:L])})
        return {"loss": list(self.loss[:FOLLOWED_STEPS]), "trees": trees,
                "init": float(self.g.init_score_value)}

    def tree_counts(self, first: int, last: int) -> list:
        """``[(rows, [(left count, right count), ...]), ...]`` of trees
        ``first..last-1``, for the work functions."""
        def count(t, child: int) -> int:
            return int(t.leaf_count[~child] if child < 0
                       else t.internal_count[child])

        return [(self.rows, [(count(t, int(t.left_child[m])),
                              count(t, int(t.right_child[m])))
                             for m in range(int(t.num_leaves) - 1)])
                for t in self.g.models[first:last]]


def grid_of(ds) -> list:
    """The finite bin bounds of each feature, as the ingest layer set
    them in set-up: the discretisation the window's trees split at."""
    out = []
    for m in ds._constructed.mappers:
        ub = np.asarray(m.bin_upper_bound, np.float64)
        out.append(ub[np.isfinite(ub)][:max(int(m.num_bin) - 1, 0)])
    return out


def rescued(before: dict, after: dict) -> list:
    """What the program's telemetry says was rescued between two
    summaries: fallback counters, degrade events, retried or exhausted
    dispatches (``chip_smoke.py``'s ``check_quiet_path``, as a delta)."""
    found = []
    c0, c1 = before["counters"], after["counters"]
    for k, v in c1.items():
        if v != c0.get(k, 0) and ("fallback" in k or k in (
                "retry.device_dispatch.retries",
                "retry.device_dispatch.exhausted")):
            found.append(f"{k} +{v - c0.get(k, 0)}")
    for k, v in after["events"].items():
        if k.startswith("degrade:") and v != before["events"].get(k, 0):
            found.append(k)
    return found


def span(summary: dict, name: str, field: str) -> float:
    return summary["spans"].get(name, {}).get(field, 0)


def against_reference(cfg: dict, program: dict, X, y, grid, log):
    """Follow the program's trees with the configuration's plain
    reference and compare; ``-> ({compared number: value}, [observed],
    the reference's own results)``."""
    from benchmark import check
    from benchmark.reference import gbdt as reference
    t0 = time.perf_counter()
    ref = reference.follow(np.ascontiguousarray(X.T), y, grid,
                           cfg["reference_params"], program["trees"],
                           program["init"], cfg["precision"]["hist_mode"],
                           log=log)
    log(f"reference: {time.perf_counter() - t0:.1f} s")
    return (*check.compare(program, ref, cfg["reference_params"]), ref)


def run(ctx) -> dict:
    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu import obs
    from benchmark import work
    obs.enable()
    params = program_params(ctx.cfg)
    X, y, ds = make_dataset(ctx, lgb)
    obs_start = obs.summary()
    b = Booster(ctx, lgb, ds, y, params)
    g = b.g
    with ctx.clock("followed_steps"):
        b.followed_steps()
    obs_setup = obs.summary()
    ctx.say(f"resolved backend: {obs_setup['gauges'].get('gbdt.hist_backend')}"
            f"  hist mode: {obs_setup['gauges'].get('gbdt.hist_mode')}")
    setup_s = ctx.since_start()
    ctx.say("set-up clocks: " + ", ".join(
        f"{k} {v:.2f} s" for k, v in ctx.clocks.items())
        + f"; block programs compiled "
        f"{span(obs_setup, 'gbdt.block_compile', 'count')} in "
        f"{span(obs_setup, 'gbdt.block_compile', 'total_s'):.2f} s")

    compiles0 = ctx.compiles()
    attempted = failed = 0

    def timed_step() -> float:
        """One step of the window; a step in which the program's
        telemetry shows a rescue counts as failed."""
        nonlocal attempted, failed
        before = obs.summary()
        b.step()
        done = time.perf_counter()
        attempted += 1
        why = rescued(before, obs.summary())
        if why:
            failed += 1
            ctx.say(f"step {attempted} rescued: {why}")
        return done

    reading = {"clocks": ctx.clocks, "obs": {"start": obs_start,
                                             "setup": obs_setup}}
    end_to_end = {"setup_s": setup_s}
    if ctx.trace:
        from jax.profiler import TraceAnnotation
        first_tree = g.num_trees()
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # the device and the spans only
        jax.profiler.start_trace(ctx.trace_dir, profiler_options=options)
        try:
            with TraceAnnotation("bench.window"):
                for _ in range(TRACED_STEPS):
                    with TraceAnnotation("bench.step"):
                        timed_step()
        finally:
            jax.profiler.stop_trace()
        reading["iterations"] = TRACED_STEPS * b.block_iters
    else:
        t0 = t1 = time.perf_counter()
        ends = []
        while t1 - t0 < ctx.seconds:
            t1 = timed_step()
            ends.append(t1 - t0)
        end_to_end["train.row_iters_per_s"] = (
            attempted * b.block_iters * b.rows / (t1 - t0))
        ctx.say(f"window: {attempted} steps of {b.block_iters} iteration(s) "
                f"in {t1 - t0:.3f} s; each ended at "
                + " ".join(f"{e:.3f}" for e in ends))
    obs_end = obs.summary()
    ctx.say(f"compilations inside the window: "
            f"{ctx.compiles() - compiles0} (XLA), "
            f"{span(obs_end, 'gbdt.block_compile', 'count') - span(obs_setup, 'gbdt.block_compile', 'count')} (block programs)")
    ctx.say("spans: gbdt.block(+_compile)="
            f"{span(obs_end, 'gbdt.block', 'count') + span(obs_end, 'gbdt.block_compile', 'count')}"
            f" gbdt.iteration={span(obs_end, 'gbdt.iteration', 'count')}")

    memory_peak = ctx.memory_peak()
    program = b.outputs()
    if ctx.trace:
        shape = {"rows": b.rows, "features": X.shape[1],
                 "bins": int(ctx.cfg["params"]["max_bin"]),
                 "leaves": int(ctx.cfg["params"]["num_leaves"]),
                 "hist_mode": params["hist_mode"]}
        trees = b.tree_counts(first_tree, first_tree + reading["iterations"])
        reading["work"] = {"histogram": work.histogram(shape, trees),
                           "iteration": work.iteration(shape, trees)}
    grid = grid_of(ds)
    del b, g, ds
    gc.collect()

    values, seen, _ = against_reference(ctx.cfg, program, X, y, grid,
                                        ctx.say)
    for line in seen:
        ctx.say("observed: " + line)
    return {"attempted": attempted, "failed": failed,
            "end_to_end": end_to_end, "reading": reading,
            "memory_peak_bytes": memory_peak, "compared": values}
