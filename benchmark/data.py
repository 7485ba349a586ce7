"""The benchmark's data rule: one generator, every number from ``--seed``.

A configuration's ``data`` group names the rule and its sizes.  Rows are
made in fixed chunks, each chunk from its own child of
``SeedSequence(seed)``, in float32 (the host never holds a float64
copy), on a few threads: the result depends on the seed and the chunk
size, never on the thread count.  The matrix is made feature-major and
handed over as its transpose, so ``X`` is ``[rows, features]`` with each
column contiguous - the layout a DataFrame's float block has, and the
one in which the program's per-column binning reads memory once.

Rule ``criteo_like`` (the reference's Criteo experiment publishes its
shape, 1.7B x 67 dense features after its own encoding, not its columns):

* ``count_features`` columns ``floor(exp(N(mu, sigma^2)))``: integer
  valued, heavy tailed, many ties, a fifth of them zero;
* the rest real valued ``N(0, 1)``, the first ``squashed_features`` of
  them pushed through a logistic to (0, 1) as rate-encoded categoricals
  would be;
* label: Bernoulli of a logistic score that is nonlinear in ten
  features (products and thresholds, so trees grow unbalanced), with
  ``intercept`` set for the stated positive rate.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

THREADS = 8


def _criteo_like_chunk(ss: np.random.SeedSequence, spec: dict,
                       XT: np.ndarray, y: np.ndarray) -> None:
    """Fill ``XT [features, rows]`` and ``y [rows]`` in place."""
    rng = np.random.Generator(np.random.PCG64(ss))
    nc = int(spec["count_features"])
    ns = int(spec["squashed_features"])
    rows = XT.shape[1]
    z = rng.standard_normal(XT.shape, dtype=np.float32)
    c = z[:nc]
    np.multiply(c, np.float32(spec["count_sigma"]), out=c)
    np.add(c, np.float32(spec["count_mu"]), out=c)
    np.exp(c, out=c)
    np.floor(c, out=c)
    u = z[nc:nc + ns]
    np.negative(u, out=u)
    np.exp(u, out=u)
    np.add(u, np.float32(1.0), out=u)
    np.reciprocal(u, out=u)
    r = z[nc + ns:]
    lc = np.log1p(c[:3])
    s = (np.float32(spec["intercept"])
         + 0.8 * r[0] - 0.6 * r[1] + 0.5 * r[2] * r[3]
         + 0.7 * (lc[0] > 1.5) - 0.9 * (lc[1] > 2.0) * (r[4] > 0)
         + 0.4 * np.abs(r[5]) + 1.8 * (u[0] - 0.5) * (u[1] > 0.6)
         + 0.3 * lc[2] * r[6])
    p = 1.0 / (1.0 + np.exp(-s.astype(np.float32)))
    y[:] = rng.random(rows, dtype=np.float32) < p
    XT[:] = z


RULES = {"criteo_like": _criteo_like_chunk}


def make(spec: dict, seed: int):
    """``-> (X float32 [rows, features], column-major; y float32 [rows])``
    from the configuration's ``data`` group and the seed."""
    rows, F = int(spec["rows"]), int(spec["features"])
    chunk = int(spec["chunk_rows"])
    fill = RULES[spec["rule"]]
    starts = list(range(0, rows, chunk))
    children = np.random.SeedSequence(int(seed)).spawn(len(starts))
    XT = np.empty((F, rows), np.float32)
    y = np.empty(rows, np.float32)

    def one(i: int) -> None:
        a = starts[i]
        b = min(a + chunk, rows)
        fill(children[i], spec, XT[:, a:b], y[a:b])

    with ThreadPoolExecutor(THREADS) as pool:
        list(pool.map(one, range(len(starts))))
    return XT.T, y
