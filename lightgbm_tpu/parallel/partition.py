"""Partition-rule sharding registry — ONE named placement mechanism.

Every persistent device array in the system is named in a flat
``/``-separated name tree and placed by matching that name against an
ordered table of ``(rule_name, regex, PartitionSpec)`` rules — the
``match_partition_rules`` + ``make_shard_and_gather_fns`` pattern of
the big-model trainers (SNIPPETS.md [1]/[2]: fmengine / EasyDeL place
params by regex once, then every step consumes them in place), applied
to the GBDT training store.  Before this module the same decisions
lived in five bespoke sites (``MeshContext.place_data`` for
bins/metadata, ad-hoc ``NamedSharding``/``with_sharding_constraint``
pairs in ``boosting/gbdt.py`` for grad/hess/bag, default-device
``device_put`` for scores/valid state, and the serve pack's implicit
``jnp.asarray`` placement) — five places a new array could silently
pick a wrong layout.

Contract (the registry-completeness gate, ``tools/partition_audit.py``
+ ``tests/test_partition.py``):

* every persistent name placed on a mesh matches **exactly one** rule
  — zero matches raise :class:`PartitionRuleError` at placement time
  (a hard error, never a silent default), and overlapping rules fail
  the audit;
* the rule table is TOTAL over the canonical persistent-name set
  (``persistent_names``): training store fields (from the real
  ``DeviceData`` fields, so a new field cannot drift out of coverage),
  scores, valid scores, grad/hess, bag/feature masks, early-stopping
  state, and the serve tree pack (from the real ``ServePack`` fields —
  registered replicated for now, proving the registry spans train AND
  serve with zero behavior change).

Name tree (flat, ``/``-joined):

==========================  =============================================
``data/<field>``            training ``DeviceData`` arrays (``data/bins``
                            row-sharded for data/voting, replicated for
                            feature-parallel; metadata replicated)
``scores``                  running train scores ``[n, K]`` (``n`` is the
                            UNPADDED row count — row padding happens
                            inside the jitted build: row-sharded with
                            the rows where the shards divide ``n``,
                            else replicated; host eval/feval/C-API
                            read them per window either way)
``valid/<i>/scores``        running valid scores (replicated)
``valid/<i>/data/<field>``  valid ``DeviceData`` arrays (replicated)
``grad`` / ``hess``         per-iteration gradient slices (row-sharded
                            for data/voting; padded inside jit first)
``bag_mask``                row-sampling mask (row-sharded, padded
                            out-of-bag inside jit)
``feature_mask``            per-tree feature mask (replicated)
``es/<key>``                early-stopping score state (replicated)
``serve/pack/<field>``      compiled ``ServePack`` arrays (replicated)
==========================  =============================================
"""
from __future__ import annotations

import re
from typing import Any, Callable, Dict, Iterable, List, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Rule = Tuple[str, str, P]


class PartitionRuleError(ValueError):
    """A persistent array name did not match exactly one partition rule."""


# ---------------------------------------------------------------------------
# rule tables
# ---------------------------------------------------------------------------
def train_rules(data_axis: str = "data", row_sharded: bool = True,
                scores_sharded: bool = False) -> Tuple[Rule, ...]:
    """The training-side rule table for one mesh context.

    ``row_sharded`` is the learner-type switch: data/voting-parallel
    shard the row axis, feature-parallel replicates rows (the learner
    slices feature columns inside the shard instead).  The regexes are
    mutually exclusive by construction (``data/bins`` is carved out of
    the metadata catch-all with a lookahead) so the completeness gate
    can demand EXACTLY one match per name.

    ``scores_sharded``: the running train scores follow the rows of a
    row-sharded learner where the row count is a multiple of the shard
    count (no padding rows to leave out): each shard then computes its
    own rows' gradients and adds its own rows' leaf values, with no
    gather across shards.  Otherwise they are replicated."""
    row = P(data_axis) if row_sharded else P()
    return (
        ("bins",         r"^data/bins$",            row),
        ("data_meta",    r"^data/(?!bins$)",        P()),
        ("scores",       r"^scores$",
         P(data_axis) if scores_sharded else P()),
        ("valid_scores", r"^valid/\d+/scores$",     P()),
        ("valid_data",   r"^valid/\d+/data/",       P()),
        ("grad_hess",    r"^(grad|hess)$",          row),
        ("bag_mask",     r"^bag_mask$",             row),
        ("feature_mask", r"^feature_mask$",         P()),
        ("es_state",     r"^es/",                   P()),
    ) + serve_rules()


def serve_rules() -> Tuple[Rule, ...]:
    """Serve-side rules: the compiled tree pack is replicated for now
    (every chip holds the whole forest; the trees-axis sharding of
    ROADMAP item 3a will refine exactly this one rule)."""
    return (("serve_pack", r"^serve/pack/", P()),)


# ---------------------------------------------------------------------------
# name trees
# ---------------------------------------------------------------------------
def device_data_names(dd) -> Dict[str, Any]:
    """``{field: array}`` for a ``DeviceData``'s ARRAY children, named
    by the real NamedTuple fields — a new persistent field shows up
    here automatically and must find a rule."""
    children, _ = dd.tree_flatten()
    return dict(zip(type(dd)._fields, children))


def serve_pack_names(pack) -> Dict[str, Any]:
    """``{field: array}`` for a ``ServePack``'s array children."""
    children, _ = pack.tree_flatten()
    return {"serve": {"pack": dict(zip(type(pack)._fields, children))}}


def flatten_names(tree: Any, sep: str = "/") -> List[Tuple[str, Any]]:
    """Flatten a dict name tree to ``[(joined_name, leaf), ...]``."""
    out: List[Tuple[str, Any]] = []

    def walk(prefix: str, node: Any) -> None:
        if isinstance(node, dict):
            for k, v in node.items():
                walk(f"{prefix}{sep}{k}" if prefix else str(k), v)
        elif isinstance(node, (list, tuple)):
            for i, v in enumerate(node):
                walk(f"{prefix}{sep}{i}" if prefix else str(i), v)
        else:
            out.append((prefix, node))

    walk("", tree)
    return out


def persistent_names(num_valid: int = 1) -> List[str]:
    """The canonical persistent-name set the audit must cover: derived
    from the REAL ``DeviceData`` / ``ServePack`` field lists (source of
    truth, not a copy) plus the booster-level state names."""
    from ..io.device import DeviceData
    names = [f"data/{f}" for f in DeviceData._fields[:9]]
    names += ["scores", "grad", "hess", "bag_mask", "feature_mask"]
    for i in range(num_valid):
        names += [f"valid/{i}/scores"]
        names += [f"valid/{i}/data/{f}" for f in DeviceData._fields[:9]]
    names += ["es/best_scores", "es/best_iter"]
    from ..serve.compiler import ServePack
    names += [f"serve/pack/{f}" for f in ServePack._fields[:-1]]
    return names


# ---------------------------------------------------------------------------
# matching
# ---------------------------------------------------------------------------
def matching_rules(rules: Sequence[Rule], name: str) -> List[str]:
    return [rn for rn, rx, _ in rules if re.search(rx, name) is not None]


def match_name(rules: Sequence[Rule], name: str) -> P:
    """The one rule's spec for ``name``; an unmatched name is a HARD
    error — a persistent array without a placement decision must fail
    loudly at placement time, not inherit a silent default layout."""
    for rule_name, rx, spec in rules:
        if re.search(rx, name) is not None:
            return spec
    raise PartitionRuleError(
        f"no partition rule matches persistent array {name!r}; add a "
        f"rule to lightgbm_tpu/parallel/partition.py (rules: "
        f"{[r[0] for r in rules]})")


def match_partition_rules(rules: Sequence[Rule], tree: Any,
                          sep: str = "/") -> Dict[str, P]:
    """``{flat_name: PartitionSpec}`` for a dict name tree.  Scalars /
    0-d leaves get ``P()`` (never partition a scalar — snippet [1]);
    every other leaf must match a rule or this raises."""
    specs: Dict[str, P] = {}
    for name, leaf in flatten_names(tree, sep):
        if np.ndim(leaf) == 0:
            specs[name] = P()
        else:
            specs[name] = match_name(rules, name)
    return specs


def audit_rules(rules: Sequence[Rule],
                names: Iterable[str]) -> List[str]:
    """The completeness gate: every name must match EXACTLY one rule.
    Returns human-readable findings (empty == clean)."""
    findings = []
    for name in names:
        hits = matching_rules(rules, name)
        if len(hits) == 0:
            findings.append(f"{name}: matches NO partition rule")
        elif len(hits) > 1:
            findings.append(
                f"{name}: matches {len(hits)} rules {hits} (must be 1)")
    return findings


# ---------------------------------------------------------------------------
# shard / gather
# ---------------------------------------------------------------------------
def make_shard_and_gather_fns(rules: Sequence[Rule], mesh: Mesh,
                              ) -> Tuple[Callable[[str, Any], Any],
                                         Callable[[Any], Any]]:
    """``(shard_fn, gather_fn)`` over a mesh: ``shard_fn(name, x)``
    places ``x`` under the matched rule's ``NamedSharding`` (host
    numpy or device arrays both accepted — one transfer, no eager
    relayout later); ``gather_fn(x)`` replicates back (the full-array
    view host readers expect)."""
    rep = NamedSharding(mesh, P())

    def shard_fn(name: str, x):
        if np.ndim(x) == 0:
            return jax.device_put(x, rep)
        return jax.device_put(x, NamedSharding(mesh, match_name(rules, name)))

    def gather_fn(x):
        return jax.device_put(x, rep)

    return shard_fn, gather_fn


def place_tree(rules: Sequence[Rule], mesh: Mesh, tree: Any,
               sep: str = "/") -> Any:
    """Place a whole dict name tree under the registry; returns a tree
    of the same structure with every array leaf device_put under its
    matched rule."""
    shard_fn, _ = make_shard_and_gather_fns(rules, mesh)

    def walk(prefix: str, node: Any) -> Any:
        if isinstance(node, dict):
            return {k: walk(f"{prefix}{sep}{k}" if prefix else str(k), v)
                    for k, v in node.items()}
        if isinstance(node, (list, tuple)):
            return type(node)(
                walk(f"{prefix}{sep}{i}" if prefix else str(i), v)
                for i, v in enumerate(node))
        return shard_fn(prefix, node)

    return walk("", tree)
