"""The names the program puts where the work happens (ISSUE 26).

Device half: ``jax.named_scope`` inside the fused block program, under
the span names of the unfused path.  They are metadata on the
operations: the lowered text carries each, and the compiled program,
metadata stripped, is the same with them and without.  Host half: every
telemetry span enters a ``jax.profiler.TraceAnnotation``, so a profiler
trace that anybody started holds the program's spans; ingest and upload
have spans of their own; a histogram mode the program replaced shows in
the summary.
"""
import contextlib
import glob
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import obs

SCOPES = ["obj.grad", "tree.pack", "tree.init", "tree.route", "tree.hist",
          "tree.split_find", "tree.update", "gbdt.score_update"]


@pytest.fixture(autouse=True)
def _clean_obs():
    obs.reset()
    yield
    obs.reset()


def _data(n=3000, f=6, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.normal(size=(n, f)).astype(np.float32)
    y = (X[:, 0] + X[:, 1] * X[:, 2] > 0).astype(np.float32)
    return X, y


def _lower_block():
    """The length-1 block program of a booster on the kernel path (what
    ``auto`` is on a TPU; interpreted off it), 80 leaves: a 64-slot
    wave among them."""
    X, y = _data()
    params = {"objective": "binary", "num_leaves": 80, "max_bin": 15,
              "min_data_in_leaf": 2, "verbose": -1}
    ds = lgb.Dataset(X, label=y, params={"max_bin": 15})
    g = lgb.Booster(params=params, train_set=ds)._gbdt
    assert g.hist_backend == "pallas"
    return g._make_block_fn(1).lower(
        g.device_data, g._bins_t, tuple(g._valid_device), g.scores,
        tuple(g._valid_scores), jnp.float32(0.1), jnp.int32(0),
        jnp.int32(1))


@pytest.fixture(scope="module")
def lowered():
    """``(with the scopes, with jax.named_scope a no-op)``."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("LGBM_TPU_HIST_BACKEND", "pallas")
        jax.clear_caches()
        scoped = _lower_block()
        jax.clear_caches()              # the jitted wrappers' traces too
        mp.setattr(jax, "named_scope",
                   lambda name: contextlib.nullcontext())
        plain = _lower_block()
    jax.clear_caches()
    return scoped, plain


@pytest.mark.parametrize("scope", SCOPES)
def test_block_program_names_its_parts(lowered, scope):
    scoped, plain = lowered
    pattern = r'[/"]' + re.escape(scope) + r'[/"]'
    assert re.search(pattern, scoped.as_text(debug_info=True))
    assert not re.search(pattern, plain.as_text(debug_info=True))


def test_scopes_leave_the_compiled_block_as_it_was(lowered):
    def stripped(low):
        return re.sub(r", metadata=\{[^}]*\}", "", low.compile().as_text())
    assert "tree.hist" in lowered[0].compile().as_text()
    scoped, plain = (stripped(low) for low in lowered)
    assert "tree.hist" not in scoped and "metadata=" not in scoped
    assert scoped == plain


@pytest.mark.parametrize("recording", ["recorded.xplane.pb.gz",
                                       "recorded_scoped.xplane.pb.gz"])
def test_the_benchmark_counts_the_compacted_waves_by_name(recording):
    """``kernels.hist_compact_calls_per_iter`` (PR 27) counts the grouped
    kernel's calls by its jitted wrapper's name: 2 an iteration on both
    traces recorded while ``auto`` still compacted (two steps each).  The
    program has held no such wrapper since PR 31, so there it reads 0."""
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    try:
        from benchmark.readers import scope_time
    finally:
        sys.path.remove(root)
    name = "kernels.hist_compact_calls_per_iter"
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        (entry,) = [m for m in json.load(f)["per_layer"]
                    if m["name"] == name]
    assert entry["moves"] == "train.row_iters_per_s"
    with open(os.path.join(root, "benchmark", "metrics",
                           name + ".json")) as f:
        spec = json.load(f)
    assert spec.pop("reader") == "scope_time" and spec["count"]
    path = os.path.join(root, "benchmark", "tests", recording)
    calls = [short for stack, short, _s in scope_time.stacked_self_times(path)
             if scope_time.matches(stack, short, spec)]
    assert len(calls) / 2 == 2


def _host_events(trace_dir) -> set:
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    return {e.name for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events}


@pytest.mark.parametrize("enabled", [True, False])
def test_spans_reach_a_trace_somebody_else_started(tmp_path, enabled):
    if enabled:
        obs.enable()
    X, y = _data(400, 5)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        bst = lgb.train({"objective": "binary", "verbose": -1},
                        lgb.Dataset(X, label=y), num_boost_round=2,
                        keep_training_booster=True)
        bst._gbdt.train(2)              # the cached program: gbdt.block
        jax.block_until_ready(bst._gbdt.scores)
    finally:
        jax.profiler.stop_trace()
    ours = {n for n in _host_events(str(tmp_path))
            if n.startswith(("gbdt.", "io.", "engine."))}
    if enabled:
        assert {"gbdt.block", "gbdt.block_compile", "gbdt.train",
                "io.construct", "io.value_to_bin", "gbdt.upload"} <= ours
    else:
        assert not ours


def test_ingest_and_upload_have_one_span_each():
    obs.enable()
    X, y = _data(500, 7)
    lgb.train({"objective": "binary", "verbose": -1},
              lgb.Dataset(X, label=y), num_boost_round=2)
    spans = obs.summary()["spans"]
    assert spans["io.find_bin"]["count"] == 7          # one a feature
    for name in ("io.construct", "io.value_to_bin", "gbdt.upload"):
        assert spans[name]["count"] == 1, name
    assert spans["io.value_to_bin"]["total_s"] <= \
        spans["io.construct"]["total_s"]


def test_span_attributes_carry_rows_and_features(tmp_path):
    path = tmp_path / "trace.jsonl"
    obs.enable(trace_path=str(path))
    X, y = _data(500, 7)
    lgb.train({"objective": "binary", "verbose": -1},
              lgb.Dataset(X, label=y), num_boost_round=1)
    obs.disable()
    recs = [json.loads(line) for line in path.read_text().splitlines()]
    for name in ("io.construct", "io.value_to_bin", "gbdt.upload"):
        rec = next(r for r in recs if r["name"] == name)
        assert (rec["rows"], rec["features"]) == (500, 7), name


@pytest.mark.parametrize("degraded", [True, False])
def test_a_replaced_hist_mode_shows_in_the_summary(monkeypatch, degraded):
    from lightgbm_tpu.learner import serial
    if degraded:
        # more parts than the limbs add exactly (a resident shard's size
        # alone replaces no mode: its rows are summed in chunks)
        monkeypatch.setattr(serial, "MAX_CODE_SHARDS", 0)
    obs.enable()
    X, y = _data(500, 5)
    bst = lgb.Booster(params={"objective": "binary", "hist_mode": "int8h",
                              "verbose": -1},
                      train_set=lgb.Dataset(X, label=y))
    s = obs.summary()
    if degraded:
        assert bst._gbdt.hist_mode == "hhilo"
        assert s["gauges"]["gbdt.hist_mode"] == "hhilo"
        assert s["gauges"]["gbdt.hist_mode_requested"] == "int8h"
        assert s["events"]["degrade:hist_mode"] == 1
    else:
        assert s["gauges"]["gbdt.hist_mode"] == "int8h"
        assert "gbdt.hist_mode_requested" not in s["gauges"]
        assert "degrade:hist_mode" not in s["events"]


def test_no_scope_stands_between_a_kernel_and_its_jitted_wrapper():
    """The TPU compiler names a custom call after the name-stack
    component right around it: ``jit(hist_active_pallas)`` gives
    ``%hist_active_pallas.N``, which the benchmark's class ``hist``
    matches.  A scope inside the wrapper around the ``pallas_call`` would
    rename the kernel (it did: ``%tree.hist.N``), so the kernel's scope
    is the caller's."""
    from lightgbm_tpu.ops.pallas_histogram import (hist_active_pallas,
                                                   pack_values_q,
                                                   transpose_bins)
    n, F = 2048, 4
    rng = np.random.RandomState(0)
    bins_t = transpose_bins(jnp.asarray(rng.randint(0, 15, size=(n, F)),
                                        jnp.uint8))
    vals, scales = pack_values_q(jnp.asarray(rng.normal(size=n), jnp.float32),
                                 jnp.ones(n, jnp.float32), "int8h")
    leaf = jnp.asarray(rng.randint(0, 40, size=bins_t.shape[1]), jnp.int32)

    def caller(*a):
        with jax.named_scope("tree.hist"):
            return hist_active_pallas(*a, num_features=F, max_bins=15,
                                      mode="int8h", interpret=True)
    jaxpr = jax.make_jaxpr(caller)(bins_t, vals, leaf,
                                   jnp.arange(40, dtype=jnp.int32), scales)
    (wrapper,) = jaxpr.jaxpr.eqns
    assert wrapper.params["name"] == "hist_active_pallas"
    assert str(wrapper.source_info.name_stack) == "tree.hist"
    stacks = {e.primitive.name: str(e.source_info.name_stack)
              for e in wrapper.params["jaxpr"].jaxpr.eqns}
    assert set(stacks.values()) == {""} and "pallas_call" in stacks


# ---------------------------------------------------------------------------
# the row-sharded exchange (ISSUE 28): collective.* on the mesh block
# ---------------------------------------------------------------------------
# per learner, every scope of its exchange: the wave's reduction, which
# `collective.hist_psum_ms_per_iter` reads, and the six names that
# `collective.other_ms_per_iter` sums
COLLECTIVES = {
    "data": ["collective.hist_psum", "collective.root_psum",
             "collective.scale_pmax", "collective.count_psum"],
    "voting": ["collective.vote_gather", "collective.sel_psum",
               "collective.root_psum", "collective.scale_pmax",
               "collective.count_psum"],
    "feature": ["collective.sync_global_best"],
}


def _lower_mesh_block(learner: str):
    """The length-1 block program of a two-shard booster on the kernel
    path (interpreted off-TPU) at ``int8h``."""
    X, y = _data()
    params = {"objective": "binary", "num_leaves": 15, "max_bin": 15,
              "min_data_in_leaf": 2, "verbose": -1, "tree_learner": learner,
              "mesh_shape": [2], "hist_mode": "int8h", "top_k": 3}
    ds = lgb.Dataset(X, label=y, params={"max_bin": 15})
    g = lgb.Booster(params=params, train_set=ds)._gbdt
    assert g.hist_backend == "pallas" and g.mesh_ctx is not None
    return g._make_block_fn(1).lower(
        g.device_data, g._bins_t, tuple(g._valid_device), g.scores,
        tuple(g._valid_scores), jnp.float32(0.1), jnp.int32(0),
        jnp.int32(1))


_MESH_TEXTS = {}


@pytest.fixture
def compiled_mesh(request):
    """``(optimized HLO with the scopes, with jax.named_scope a no-op,
    the unscoped lowering's own text)`` of the mesh block of the learner
    that is the test's (indirect) parameter, compiled once a learner.
    The persistent compile cache is off meanwhile: its key leaves
    metadata out, so the second compile would be handed the first one's
    program, names and all."""
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 virtual devices")
    if request.param in _MESH_TEXTS:
        return _MESH_TEXTS[request.param]
    from jax.experimental.compilation_cache import compilation_cache
    from lightgbm_tpu.learner import serial
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()     # or the switch is not looked at
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("LGBM_TPU_HIST_BACKEND", "pallas")
            # the integer recount of the leaves' rows, which the program
            # runs past 2^24 rows in all
            mp.setattr(serial, "F32_EXACT_ROWS", 0)
            texts = []
            for scoped in (True, False):    # one call site: the program
                jax.clear_caches()          # text holds its line numbers
                if not scoped:
                    mp.setattr(jax, "named_scope",
                               lambda name: contextlib.nullcontext())
                low = _lower_mesh_block(request.param)
                texts.append(low.compile().as_text())
            texts.append(low.as_text(debug_info=True))
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)
        compilation_cache.reset_cache()
        jax.clear_caches()
    return _MESH_TEXTS.setdefault(request.param, tuple(texts))


@pytest.mark.parametrize(
    "compiled_mesh,scope",
    [(learner, scope) for learner, scopes in COLLECTIVES.items()
     for scope in scopes], indirect=["compiled_mesh"])
def test_the_exchange_is_named_on_the_optimized_program(compiled_mesh,
                                                        scope):
    """Every collective of a mesh block carries a ``collective.*``
    scope in the optimized HLO's metadata, and every scope the
    benchmark reads names one of its learner's."""
    scoped, plain, plain_lowered = compiled_mesh
    named = [line for line in scoped.splitlines()
             if re.search(r" all-(reduce|gather)(-start)?\(", line)]
    assert named and all("collective." in line for line in named), named
    assert any(scope + "/" in line for line in named), scope
    assert scope not in plain and scope not in plain_lowered


@pytest.mark.parametrize("compiled_mesh", list(COLLECTIVES), indirect=True)
def test_the_collective_scopes_leave_the_compiled_block_as_it_was(
        compiled_mesh):
    def bare(text):
        """Without metadata and without the instructions' names, which
        are made from it (``%broadcast_in_dim.987`` with the scopes,
        ``%broadcast_in_dim_broadcast_in_dim.992`` without): every
        operation, shape, layout and attribute, in the order they run."""
        text = re.sub(r", metadata=\{[^}]*\}", "", text)
        return re.sub(r"%[\w\-.]+", "%", text)
    scoped, plain = (bare(text) for text in compiled_mesh[:2])
    assert "collective." not in scoped
    assert re.search(r" all-(reduce|gather)\(", scoped)
    assert scoped == plain
