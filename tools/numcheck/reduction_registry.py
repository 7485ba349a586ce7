"""Declarative reduction registry — the ground truth for NUM001/NUM005.

The byte-identity contract (PR 11/14/16) holds only because every
floating-point reduction whose result feeds persistent state is either

* a **canonical reducer** — an explicit, order-pinned reduction tree
  (``learner/serial.py``'s ``_pairwise_halve`` family) that XLA cannot
  legally reassociate, so serial / streamed / elastic partitionings
  reassemble bit-identical scalars from per-block partials; or
* a **partition-independent sum** — a reduction whose operand order can
  never vary with the partitioning (per-query pair grids, per-tree
  axes, single-nonzero selections), so raw ``jnp.sum`` is exact-enough
  by construction and stays sanctioned HERE, with its argument written
  down.

Everything else is a NUM001 finding: the exact bug class PR 14 had to
retrofit out when a raw ``jnp.sum`` over the root statistics silently
broke partition-invariance.

Each entry names its module (root-relative), the function whose BODY may
raw-reduce (for ``contexts``) or which IS the sanctioned reducer (for
``reducers``), and the one-line justification.  The NUM000 project rule
validates every entry resolves to a real function in a real module, so
the registry can never drift into fiction.
"""
from __future__ import annotations

# -- canonical reducers ----------------------------------------------------
# Functions that ARE the order-pinned reduction discipline.  Raw
# reductions inside their bodies are the implementation of the
# contract, not a violation of it.
REDUCERS = (
    {"name": "_pairwise_halve",
     "module": "lightgbm_tpu/learner/serial.py",
     "why": "explicit pairwise a+b halving tree: IEEE-defined adds XLA "
            "cannot reassociate, identical in every fusion context"},
    {"name": "root_chunk_sums",
     "module": "lightgbm_tpu/learner/serial.py",
     "why": "fixed STREAM_CHUNK grid anchored at row 0, zero-padded: "
            "per-block folds reassemble the identical [3, m] partials"},
    {"name": "reduce_chunk_sums",
     "module": "lightgbm_tpu/learner/serial.py",
     "why": "pads the chunk axis to a power of two and pairwise-halves: "
            "the tree depends only on m, never on the partitioning"},
    {"name": "root_stats",
     "module": "lightgbm_tpu/learner/serial.py",
     "why": "composition of the two canonical stages (the PR 14 "
            "retrofit that replaced the raw jnp.sum)"},
    {"name": "root_code_sums",
     "module": "lightgbm_tpu/learner/serial.py",
     "why": "root totals of the quantized modes: int32 sums of int8 "
            "codes (<= n*127 < 2^31 under effective_hist_mode's row "
            "bound) are exact, so order-free and additive over blocks"},
)

# -- partition-independent contexts ----------------------------------------
# Functions whose raw reductions are sanctioned because the operand
# order is a pure function of (data, config) — it cannot vary with how
# rows are partitioned across devices, blocks, or shards.
CONTEXTS = (
    {"function": "_select_miss_bin",
     "module": "lightgbm_tpu/ops/split.py",
     "why": "single-nonzero selection: is_miss_cell is one-hot over the "
            "bin axis, so the sum picks exactly one histogram cell — "
            "exact in any order"},
    {"function": "_fold_pair_grid",
     "module": "lightgbm_tpu/objective/objectives.py",
     "why": "lambdarank per-query [T, T] pair-grid folds: rows of one "
            "query are never split across partitions (ranking descopes "
            "row-blocked streaming), so the fold order is fixed by the "
            "in-query sort alone"},
    {"function": "_sum_tree_axis",
     "module": "lightgbm_tpu/models/tree.py",
     "why": "per-tree axis sum: trees are replicated model state and "
            "the tree axis is never partitioned, so the operand order "
            "is partition-independent"},
    {"function": "_select_row_leaf",
     "module": "lightgbm_tpu/learner/serial.py",
     "why": "single-nonzero selection: each row is in exactly one leaf, "
            "so the leaf-axis sum picks one value — exact in any order"},
    {"function": "_abs_grad_importance",
     "module": "lightgbm_tpu/boosting/variants.py",
     "why": "GOSS per-row class-axis sum: the class axis K is never "
            "partitioned (rows shard, classes replicate), and the "
            "importance only ranks rows — order is partition-"
            "independent"},
    {"function": "make_hist_fold_fn",
     "module": "lightgbm_tpu/learner/serial.py",
     "why": "accumulator-SEEDED streamed kernel folds (ISSUE 20): each "
            "block's kernel call seeds its output from the carry via "
            "input_output_aliases, replaying the monolithic kernel's "
            "adds in the monolithic order — exact int32 on quantized "
            "modes, identical per-tile f32 add sequence on the "
            "float modes; pinned "
            "streamed==resident per backend by tests/test_streaming.py"},
    {"function": "_fold_scales",
     "module": "lightgbm_tpu/boosting/streaming.py",
     "why": "per-(tree, shard) quantization scales as a chunked host "
            "absmax: f32 max/abs are exact and order-independent "
            "(idempotent commutative max, no rounding), so the chunked "
            "host reduction equals the device max(|x|) bitwise"},
)

# the explicit cross-device combine seam: psum/all-reduce of per-shard
# partials is elementwise in device order — the documented combine
# point, not a reassociation hazard (reordering happens ABOVE it, at
# shard granularity, which the shard protocol pins)
PSUM_FUNCS = frozenset({"psum", "all_reduce", "allreduce", "pmean"})

# -- persistent-state name flow (NUM001 taint) -----------------------------
# identifiers that mark an array as flowing from persistent training
# state: gradients, hessians, scores, histograms and their local
# aliases.  Matching is by exact id or substring, mirroring the other
# walls' coarse name-based resolution.
STATE_EXACT = frozenset({
    "g", "h", "G", "H", "gg", "hh", "gb", "hb", "signed", "per_tree",
})
STATE_SUBSTRINGS = (
    "grad", "hess", "score", "hist", "leaf_value",
)

# -- fenced state (NUM005) -------------------------------------------------
# score-state names whose mul+add updates must go through the PR 11/14
# fence discipline (optimization_barrier + pre-scaled .at[].add / the
# scale-then-gather shape) — a bare `scores = scores + lr * x` invites
# FMA contraction with partition-dependent last-ulp rounding.
FENCED_STATE = frozenset({
    "scores", "vscores", "valid_scores", "new_scores", "vs",
})
# fence helpers: functions registered as the blessed update shapes
FENCE_CONTEXTS = (
    {"function": "_make_block_fn",
     "module": "lightgbm_tpu/boosting/gbdt.py",
     "why": "the fenced block body: optimization_barrier + pre-scaled "
            ".at[].add updates (the PR 11 mesh discipline)"},
    {"function": "_score_update_fn",
     "module": "lightgbm_tpu/boosting/streaming.py",
     "why": "streamed per-block update compiled to the same fenced "
            "scale-then-gather shape as the in-memory body"},
)

# -- compensation idioms (NUM002) ------------------------------------------
# functions whose wide->narrow casts are COMPENSATED: the narrowing is
# paired with a residual (Neumaier / hi-lo split), so no precision is
# silently dropped.
COMPENSATED = (
    {"function": "split_hi_lo",
     "module": "lightgbm_tpu/ops/pallas_histogram.py",
     "why": "hi/lo split: x == hi + lo exactly; the narrow halves "
            "carry the full value between them"},
    {"function": "build_pack",
     "module": "lightgbm_tpu/serve/compiler.py",
     "why": "serve compiler hi/lo leaf pairs: lo = f32(v64 - f64(hi)) "
            "is the Neumaier residual of the narrowing cast"},
    {"function": "_f32_floor",
     "module": "lightgbm_tpu/serve/compiler.py",
     "why": "directed rounding, not accumulation: the narrowing is the "
            "documented threshold-floor contract (<= in f64 iff <= in "
            "f32 against the floored threshold)"},
)

# -- exact-identity comparison contexts (NUM003) ---------------------------
# operand-name substrings under which float == / != is sanctioned:
# digest/byte/text identity is the CONTRACT (byte-identical models),
# not a tolerance question.
EXACT_IDENTITY_SUBSTRINGS = (
    "digest", "hash", "sha", "bytes", "text", "fingerprint", "hexd",
)
# float-state operand names that make an == / != comparison a hazard
FLOAT_EQ_SUBSTRINGS = (
    "score", "metric", "loss", "gain", "grad", "hess", "auc",
    "leaf_value", "threshold",
)


def context_index():
    """(module, function) -> why, over every sanctioned-context table."""
    out = {}
    for table in (REDUCERS, CONTEXTS, FENCE_CONTEXTS, COMPENSATED):
        for d in table:
            out[(d["module"], d.get("function") or d["name"])] = d["why"]
    return out
