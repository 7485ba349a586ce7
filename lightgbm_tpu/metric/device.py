"""Device forms of the binary metrics: the numbers ``metrics.py`` works
out on the host from fetched scores, worked out where the scores live.

One jitted program a set evaluates every metric that has a form here
(``binary_logloss``, ``binary_error``, ``auc``) from the set's resident
scores, labels and weights, under the scope ``gbdt.eval``; what crosses
to the host is a few dozen ``uint32`` sums a metric, finished there in
Python integers.  x64 stays off: every sum that could pass 32 bits is
carried as 16-bit halves (:func:`exact_sum`).

* AUC is exact: the Mann-Whitney statistic over the float32 scores with
  tied scores sharing their rank, as :func:`metrics.binary_auc` has it.
  One ``lax.sort`` of the scores with the labels (and the weights) as
  payload; a tie block's first and last row reach its rows by a running
  maximum and minimum; then ``2 * area = sum over positives of
  (weight before the block + weight up to its end) - P**2`` in
  integers, ``P`` the positives' weight.  Weights enter as the integers
  they are multiples of (:func:`integer_weights`), in limbs narrow
  enough that a running sum over the set stays under 32 bits.
* Log-loss and error are means of a per-row float32 value; the rows'
  values are summed as 26-bit fixed point, exactly and in any order, so
  the mean does not depend on how the device reduces.
"""
from __future__ import annotations

import functools
import threading
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_HALF = 0xFFFF
_CHUNK = 1 << 16
_ROW = 1024
_ALL_ONES = 0xFFFFFFFF
# a row's value times 2**FIXED_BITS is summed as an integer: the largest
# log-loss of a row, -log(1e-15) = 34.54 (the host's clip), fits 32 bits
FIXED_BITS = 26
LOSS_CAP = 34.538776394910684
MAX_LIMBS = 8


def exact_sum(x) -> jnp.ndarray:
    """``uint32 [n] -> uint32 [4]``, whatever the sum's size: 16-bit
    halves summed in chunks of 65,536, and the chunks' sums as halves
    again (``n < 2**31``).  :func:`to_int` puts the four together."""
    x = x.astype(jnp.uint32)
    n = x.shape[0]
    c = min(_CHUNK, max(n, 1))
    x = jnp.pad(x, (0, (-n) % c)).reshape(-1, c)
    out = []
    for half in (x & _HALF, x >> 16):
        part = jnp.sum(half, axis=1, dtype=jnp.uint32)
        out += [jnp.sum(part & _HALF, dtype=jnp.uint32),
                jnp.sum(part >> 16, dtype=jnp.uint32)]
    return jnp.stack(out)


def _doubling(op, x, identity, reverse: bool):
    """Running ``op`` along axis 0 by shifts of 1, 2, 4, ...: after the
    step of ``d`` every element holds the ``op`` of the ``2 d`` elements
    up to it (from it, with ``reverse``)."""
    d = 1
    while d < x.shape[0]:
        fill = jnp.full((d,) + x.shape[1:], identity, x.dtype)
        x = op(x, jnp.concatenate([x[d:], fill] if reverse
                                  else [fill, x[:-d]], axis=0))
        d *= 2
    return x


def running(op, x, identity: int, reverse: bool = False) -> jnp.ndarray:
    """Running sum, maximum or minimum of ``x [n]`` (``uint32``) from
    its first element on, or with ``reverse`` from its last: within
    chunks of 1,024 laid along the major axis, where a shift is a move
    of whole rows, then over the chunks' results.  (``lax.cummax`` of
    millions of elements took the chip's compiler one to two minutes and
    9 ms a scan, in fusions that carry no name; this is ten shifted
    passes that carry the scope they are written under.)"""
    n = x.shape[0]
    edge = jnp.full(1, identity, jnp.uint32)
    x = jnp.pad(x.astype(jnp.uint32), (0, (-n) % _ROW),
                constant_values=edge[0]).reshape(-1, _ROW).T
    within = _doubling(op, x, edge[0], reverse)
    chunks = _doubling(op, within[0 if reverse else -1], edge[0], reverse)
    carried = (jnp.concatenate([chunks[1:], edge]) if reverse
               else jnp.concatenate([edge, chunks[:-1]]))
    return op(within, carried[None, :]).T.reshape(-1)[:n]


def to_int(s) -> int:
    """The integer an :func:`exact_sum` stands for."""
    s = [int(v) for v in np.asarray(s).reshape(4)]
    return s[0] + ((s[1] + s[2]) << 16) + (s[3] << 32)


class IntegerWeights(NamedTuple):
    """A set's weights as integer multiples of one quantum."""
    words: Tuple[np.ndarray, ...]   # uint32 [n] each: the limbs, packed
    limb_bits: int
    limbs: int
    quantum: float                  # weight = integer * quantum
    positive: int                   # sum of the positives' integers
    negative: int


def limb_bits_for(n: int) -> int:
    """Widest limb (at most 8 bits) whose running sum over ``n`` rows,
    taken twice, stays under 32 bits; 0 where there is none."""
    return min(8, (2 ** 31 // max(n, 1)).bit_length() - 1)


def _int_sum(a: np.ndarray) -> int:
    return (int(np.sum(a >> np.uint64(32), dtype=np.uint64)) << 32) \
        + int(np.sum(a & np.uint64(0xFFFFFFFF), dtype=np.uint64))


def integer_weights(weight, is_pos) -> Optional[IntegerWeights]:
    """``weight`` (or None: every row weighs 1) as integers; None where
    the weights do not fit the device's integers (negative or not
    finite, all zero, or a range past ``MAX_LIMBS`` limbs)."""
    n = len(is_pos)
    wb = limb_bits_for(n)
    if wb < 1:
        return None
    if weight is None:
        p = int(np.count_nonzero(is_pos))
        return IntegerWeights((), 1, 1, 1.0, p, n - p)
    w = np.asarray(weight, np.float64)
    if not np.all(np.isfinite(w)) or np.any(w < 0) or not np.any(w > 0):
        return None
    e = np.frexp(w[w > 0])[1]
    if int(e.max()) - int(e.min()) + 24 > 53:
        return None
    q_exp = int(e.min()) - 24           # a float32 has 24 bits below e
    ints = np.ldexp(w, -q_exp).astype(np.uint64)
    low = int(np.bitwise_or.reduce(ints))
    shift = (low & -low).bit_length() - 1
    ints >>= np.uint64(shift)
    limbs = -(-int(ints.max()).bit_length() // wb)
    if limbs > MAX_LIMBS:
        return None
    per = 32 // wb
    mask = np.uint64((1 << wb) - 1)
    words = []
    for first in range(0, limbs, per):
        word = np.zeros(n, np.uint64)
        for j in range(min(per, limbs - first)):
            word |= ((ints >> np.uint64(wb * (first + j))) & mask) \
                << np.uint64(wb * j)
        words.append(word.astype(np.uint32))
    pos = np.asarray(is_pos, bool)
    return IntegerWeights(tuple(words), wb, limbs,
                          float(np.ldexp(1.0, q_exp + shift)),
                          _int_sum(ints[pos]), _int_sum(ints[~pos]))


_SIGN = 0x80000000


def _order_key(score) -> jnp.ndarray:
    """Float32 scores as ``uint32`` keys in the scores' order, the two
    zeros one key and every NaN last, as the host's sort has them.  (The
    chip's compiler takes a third of the time over a sort by integer
    keys that it takes over one by floats.)"""
    # numcheck: disable=NUM003 -- both zeros are the one score 0, as
    # the host's comparison has them: exact by intent
    zero = score == 0
    score = jnp.where(jnp.isnan(score), jnp.nan,
                      jnp.where(zero, 0.0, score))
    bits = jax.lax.bitcast_convert_type(score, jnp.uint32)
    sign = jnp.uint32(_SIGN)
    return jnp.where(bits >= sign, ~bits, bits | sign)


def _score_of(key) -> jnp.ndarray:
    bits = jnp.where(key >= jnp.uint32(_SIGN), key & jnp.uint32(_SIGN - 1),
                     ~key)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _auc_sums(score, is_pos, words, limb_bits: int, limbs: int):
    """``-> uint32 [limbs, limbs, 2, 4]``: for the positives' limb ``l``
    and the running sums' limb ``m``, the exact sums of ``limb_l * (low
    half of R_m)`` and ``limb_l * (high half of R_m)`` over the
    positive rows, ``R_m`` a row's weight before its tie block plus the
    weight up to the block's end."""
    n = score.shape[0]
    sorted_ = jax.lax.sort((_order_key(score), is_pos.astype(jnp.int32))
                           + tuple(words), num_keys=1, is_stable=False)
    s, pos, words = _score_of(sorted_[0]), sorted_[1] > 0, sorted_[2:]
    differs = s[1:] != s[:-1]
    edge = jnp.ones(1, bool)
    is_start = jnp.concatenate([edge, differs])
    is_end = jnp.concatenate([differs, edge])
    if words:
        per = 32 // limb_bits
        mask = jnp.uint32((1 << limb_bits) - 1)
        limb = [(words[m // per] >> jnp.uint32(limb_bits * (m % per))) & mask
                for m in range(limbs)]
        upto = [running(jnp.add, a, 0) for a in limb]
    else:
        limb = [jnp.ones(n, jnp.uint32)]
        upto = [jnp.arange(1, n + 1, dtype=jnp.uint32)]
    rank2 = []
    for a, c in zip(limb, upto):
        # both running sums never fall, so a block's first "before" is
        # the largest seen so far and its last "up to" the least to come
        before = running(jnp.maximum, jnp.where(is_start, c - a, 0), 0)
        through = running(
            jnp.minimum, jnp.where(is_end, c, jnp.uint32(_ALL_ONES)),
            _ALL_ONES, reverse=True)
        rank2.append(before + through)
    return jnp.stack([
        jnp.stack([jnp.stack([exact_sum(p * (r & _HALF)),
                              exact_sum(p * (r >> 16))]) for r in rank2])
        for p in (jnp.where(pos, a, 0) for a in limb)])


def auc_from_sums(sums, iw: IntegerWeights) -> float:
    """The host's end of :func:`_auc_sums`: Python integers, one
    division."""
    sums = np.asarray(sums)
    twice_area = -iw.positive * iw.positive
    for l in range(iw.limbs):
        for m in range(iw.limbs):
            lo, hi = to_int(sums[l, m, 0]), to_int(sums[l, m, 1])
            twice_area += (lo + (hi << 16)) << (iw.limb_bits * (l + m))
    return twice_area / (2 * iw.positive * iw.negative)


def _fixed(value):
    """A row's value in ``[0, LOSS_CAP]`` as 26-bit fixed point."""
    return jnp.round(value * float(1 << FIXED_BITS)).astype(jnp.uint32)


_LN2_HI = 0.693145751953125          # ln 2 to 11 bits: k * _LN2_HI is exact
_LN2_LO = 1.42860682030941723212e-6


def _log1p_exp_neg(t):
    """``log(1 + exp(-t))`` for ``t >= 0`` from float32 additions,
    products and one division only.  The chip's own ``exp`` and ``log``
    put a mean log-loss 2e-05 to 6e-05 off the float64 value (read on a
    v5e, PERF.md PR 33); this stays within 2e-07 of it a row.

    ``exp(-t) = 2**-k * exp(-r)`` with ``r = t - k ln 2`` in ``[-ln 2 /
    2, ln 2 / 2]`` (a degree-8 series, 2e-09); then ``log(1 + e) = 2
    atanh(e / (2 + e))`` as its odd series to the 17th power (the
    argument is at most a third: 1e-09)."""
    t = jnp.minimum(t, 80.0)
    k = jnp.floor(t * 1.4426950408889634 + 0.5)
    r = (t - k * _LN2_HI) - k * _LN2_LO
    series = 1.0 / 40320.0
    for c in (-1.0 / 5040, 1.0 / 720, -1.0 / 120, 1.0 / 24, -1.0 / 6, 0.5,
              -1.0, 1.0):
        series = series * r + c
    two_to_minus_k = jax.lax.bitcast_convert_type(
        (127 - k.astype(jnp.int32)) << 23, jnp.float32)
    e = series * two_to_minus_k
    s = e / (2.0 + e)
    s2 = s * s
    odd = 1.0 / 17.0
    for c in (1.0 / 15, 1.0 / 13, 1.0 / 11, 1.0 / 9, 1.0 / 7, 0.2,
              1.0 / 3, 1.0):
        odd = odd * s2 + c
    return 2.0 * s * odd


def _row_logloss(score, label, sigmoid: float):
    """``-(y log p + (1 - y) log(1 - p))`` at ``p`` the link of the
    score, as ``y softplus(-z) + (1 - y) softplus(z)``, each term capped
    at the host's clip of ``p`` to ``1e-15``."""
    z = sigmoid * score
    tail = _log1p_exp_neg(jnp.abs(z))
    return (label * jnp.minimum(jnp.maximum(-z, 0.0) + tail, LOSS_CAP)
            + (1.0 - label) * jnp.minimum(jnp.maximum(z, 0.0) + tail,
                                          LOSS_CAP))


def _row_error(score, label, sigmoid: float):
    del sigmoid
    # numcheck: disable=NUM003 -- the host's own test: a 0/1 prediction
    # against a 0/1 label, exact by construction
    return ((score > 0).astype(jnp.float32) != label).astype(jnp.float32)


ROW_VALUES = {"binary_logloss": _row_logloss, "binary_error": _row_error}
FORMS = tuple(ROW_VALUES) + ("auc",)


@functools.partial(jax.jit, static_argnames=("forms", "sigmoid",
                                             "limb_bits", "limbs"))
def evaluate(score, label, weight_share, words, *, forms, sigmoid,
             limb_bits, limbs):
    """Every metric of ``forms`` over one set: ``score [rows >= n, 1]``
    and ``label [n]`` f32, ``weight_share`` each row's weight over the
    largest (None: unweighted), ``words`` the integer weights' limbs.
    ``-> {form: uint32 sums}`` and ``"nan"``: whether a score is not a
    number."""
    with jax.named_scope("gbdt.eval"):
        score = score[:label.shape[0], 0]
        out = {"nan": jnp.any(jnp.isnan(score))}
        for form in forms:
            if form == "auc":
                out[form] = _auc_sums(score, label > 0, words, limb_bits,
                                      limbs)
                continue
            value = ROW_VALUES[form](score, label, sigmoid)
            if weight_share is not None:
                value = value * weight_share
            out[form] = exact_sum(_fixed(value))
        return out


class EvalSet:
    """One data set's side of the device metrics: what is uploaded once
    (labels, weights) and what the host keeps to finish the sums."""

    def __init__(self, label: np.ndarray, weight: Optional[np.ndarray],
                 label_dev=None):
        label = np.asarray(label, np.float32)
        self.n = len(label)
        self.iw = integer_weights(weight, label > 0)
        self.label = (label_dev if label_dev is not None
                      else jnp.asarray(label))
        self._programs: dict = {}
        self._requested: set = set()
        self._compiling = threading.Lock()
        self.weight_share = None
        self.weight_scale = 1.0 / max(self.n, 1)     # value sum -> mean
        self.words = ()
        if weight is not None and self.iw is not None:
            w = np.asarray(weight, np.float32)
            top = float(w.max())
            self.weight_share = jnp.asarray(w / np.float32(top))
            self.weight_scale = top / float(np.sum(w, dtype=np.float64))
            self.words = tuple(jnp.asarray(x) for x in self.iw.words)

    @property
    def usable(self) -> bool:
        return self.n > 0 and self.iw is not None

    def program(self, score, forms: Tuple[str, ...], sigmoid: float):
        """The compiled evaluation of ``forms`` over scores shaped and
        placed as ``score`` (an array or its ``ShapeDtypeStruct``);
        compiled once, at the first call.  The boosting loop makes that
        call on a thread of its own before its first window, beside the
        block program's compile, so that nothing compiles between
        windows; a caller that needs the program meanwhile waits for
        it."""
        iw = self.iw
        forms = self._forms(forms)
        key = (forms, float(sigmoid), score.shape)
        with self._compiling:
            if key not in self._programs:
                self._programs[key] = evaluate.lower(
                    score, self.label, self.weight_share, self.words,
                    forms=forms, sigmoid=float(sigmoid),
                    limb_bits=iw.limb_bits, limbs=iw.limbs).compile()
            return self._programs[key]

    def _forms(self, forms: Tuple[str, ...]) -> Tuple[str, ...]:
        if self.iw.positive == 0 or self.iw.negative == 0:
            return tuple(f for f in forms if f != "auc")
        return forms

    def first_request(self, score, forms: Tuple[str, ...],
                      sigmoid: float) -> bool:
        """True once a program: the caller that is told so compiles it
        ahead of its first use (:meth:`program`, on a thread)."""
        key = (self._forms(forms), float(sigmoid), score.shape)
        if key in self._requested:
            return False
        self._requested.add(key)
        return True

    def eval(self, score, forms: Tuple[str, ...], sigmoid: float) -> dict:
        """``{form: value}`` of the set's scores ``[rows >= n, 1]`` (a
        device array), as floats; an AUC of None where a class is
        absent."""
        got = jax.device_get(self.program(score, forms, sigmoid)(
            score, self.label, self.weight_share, self.words))
        out = {}
        for form in forms:
            if form == "auc":
                out[form] = (auc_from_sums(got[form], self.iw)
                             if form in got else None)
            elif got["nan"]:
                out[form] = float("nan")
            else:
                out[form] = (to_int(got[form]) / float(1 << FIXED_BITS)
                             * self.weight_scale)
        return out
