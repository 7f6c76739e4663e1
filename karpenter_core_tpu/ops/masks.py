"""Requirement-set algebra as boolean-mask kernels.

The tensorized form of karpenter_core_tpu.scheduling.{requirement,requirements}
(which mirror /root/reference/pkg/scheduling/requirement.go:117-150 and
requirements.go:123-206).  At snapshot-encode time every label key's value
universe is finite, so a Requirement over key k becomes a boolean mask over
``V_k + 1`` slots — the final slot means "values outside the vocabulary" and
carries the complement bit: an In set has other=0, a NotIn/Exists complement
has other=1.  Gt/Lt bounds ride as separate ±inf float planes; overlap through
*unseen* values is then computed exactly: two complements overlap outside the
vocabulary iff their combined integer range (or the unbounded string universe)
contains at least one value not in the vocabulary.

With that encoding:
  - Intersection            = elementwise AND + bound max/min
  - "intersection nonempty" = any(AND) | unseen-range overlap
  - Compatible / Intersects = masked all-reductions over keys (below)

Two storage layouts share one API.  The classic layout keeps the slots as a
bool plane ``[..., K, V+1]``; the *bit-packed* layout stores the same slots as
uint32 words ``[..., K, ceil((V+1)/32)]`` (``pack_mask``), which shrinks the
solve kernel's scan carry up to 32× and turns every slot reduction into a
word-wide AND + nonzero test.
A ReqTensor is packed iff ``mask.dtype == uint32``; packed callers must pass
``v`` — the semantic slot count V+1 — because the word plane cannot recover
it.  The solve kernel runs on the packed layout alone; the bool layout is
what models/snapshot.py encodes and the reference the packed ops are tested
against (tests/test_masks.py).

All functions broadcast over leading batch axes and are jit/vmap-safe.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax.numpy as jnp
import numpy as np

NEG_INF = -jnp.inf
POS_INF = jnp.inf

WORD = 32  # bits per packed mask word


def words_for(v: int) -> int:
    """Packed words needed for ``v`` slots."""
    return -(-int(v) // WORD)


def pack_mask(mask: jnp.ndarray) -> jnp.ndarray:
    """uint32[..., W] bit-packing of bool[..., M]: bit j of word w is slot
    ``w*32+j``.  Pad bits beyond M are zero (reductions never see phantom
    slots).  jit/vmap-safe; also accepts numpy input."""
    m = mask.shape[-1]
    pad = (-m) % WORD
    if pad:
        mask = jnp.pad(mask, [(0, 0)] * (mask.ndim - 1) + [(0, pad)])
    grouped = mask.reshape(mask.shape[:-1] + (-1, WORD)).astype(jnp.uint32)
    weights = jnp.uint32(1) << jnp.arange(WORD, dtype=jnp.uint32)
    return jnp.sum(grouped * weights, axis=-1, dtype=jnp.uint32)


def unpack_mask(words: jnp.ndarray, m: int) -> jnp.ndarray:
    """bool[..., m] inverse of pack_mask."""
    shifts = jnp.arange(WORD, dtype=jnp.uint32)
    bits = (words[..., None] >> shifts) & jnp.uint32(1)
    flat = bits.reshape(bits.shape[:-2] + (-1,))
    return flat[..., :m] != 0


@functools.lru_cache(maxsize=64)
def full_words(v: int) -> np.ndarray:
    """uint32[W] constant with bits 0..v-1 set (all semantic slots)."""
    bits = np.ones(v, dtype=bool)
    pad = (-v) % WORD
    bits = np.pad(bits, (0, pad))
    weights = (np.uint32(1) << np.arange(WORD, dtype=np.uint32)).astype(np.uint32)
    return (bits.reshape(-1, WORD) * weights).sum(axis=-1).astype(np.uint32)


@functools.lru_cache(maxsize=64)
def vocab_words(v: int) -> np.ndarray:
    """uint32[W] constant selecting the V in-vocabulary slots (drops the
    trailing "unseen" slot v-1)."""
    w = full_words(v).copy()
    w[(v - 1) // WORD] &= ~np.uint32(1 << ((v - 1) % WORD))
    return w


def other_bit(words: jnp.ndarray, v: int) -> jnp.ndarray:
    """bool[...]: the trailing "unseen values" slot of a packed mask."""
    return (words[..., (v - 1) // WORD] & jnp.uint32(1 << ((v - 1) % WORD))) != 0


def not_words(words: jnp.ndarray, v: int) -> jnp.ndarray:
    """Slot-complement of a packed mask (pad bits stay zero)."""
    return ~words & jnp.asarray(full_words(v))


def is_packed(t: "ReqTensor") -> bool:
    return t.mask.dtype == jnp.uint32


class ReqTensor(NamedTuple):
    """A batch of requirement sets in mask form.

    mask:     bool[..., K, V+1]  allowed vocabulary values per key (undefined
                                 keys = all ones); slot V = "unseen values".
                                 Bit-packed layout: uint32[..., K, W] words
                                 over the same slots (see pack_mask; callers
                                 pass ``v`` = V+1 to the ops below)
    defined:  bool[..., K]       key explicitly present
    negative: bool[..., K]       operator is NotIn or DoesNotExist
    gt:       f32[..., K]        exclusive lower bound (-inf when absent)
    lt:       f32[..., K]        exclusive upper bound (+inf when absent)
    """

    mask: jnp.ndarray
    defined: jnp.ndarray
    negative: jnp.ndarray
    gt: jnp.ndarray
    lt: jnp.ndarray


def pack_req(t: ReqTensor) -> ReqTensor:
    """Bit-pack a bool-layout ReqTensor's mask plane (no-op when packed)."""
    if is_packed(t):
        return t
    return t._replace(mask=pack_mask(t.mask))


def _other_slot(mask: jnp.ndarray, v: Optional[int]) -> jnp.ndarray:
    if mask.dtype == jnp.uint32:
        return other_bit(mask, v)
    return mask[..., -1]


def _unseen_range_overlap(
    gt: jnp.ndarray, lt: jnp.ndarray, vocab_ints: Optional[jnp.ndarray]
) -> jnp.ndarray:
    """bool[..., K]: the combined (gt, lt) range admits some value OUTSIDE the
    vocabulary.  With no bounds the unseen string universe is infinite.  With
    bounds, only integers strictly inside (gt, lt) qualify
    (requirement.go:227-243 withinIntPtrs rejects non-ints under bounds); the
    count of such integers minus those already in the vocabulary must be
    positive.  ``vocab_ints`` is f32[K, V] — each key's vocabulary values as
    numbers, +inf where non-numeric (never inside a finite range)."""
    # number of integers strictly between the bounds (inf when unbounded)
    n_range = jnp.maximum(jnp.ceil(lt) - jnp.floor(gt) - 1.0, 0.0)
    if vocab_ints is None:
        n_vocab_in_range = jnp.zeros_like(gt)
    else:
        inside = (vocab_ints > gt[..., None]) & (vocab_ints < lt[..., None])
        n_vocab_in_range = jnp.sum(inside.astype(jnp.float32), axis=-1)
    return n_range - n_vocab_in_range >= 1.0


def _unseen_overlap(
    a: ReqTensor, b: ReqTensor, vocab_ints: Optional[jnp.ndarray],
    v: Optional[int] = None,
) -> jnp.ndarray:
    """bool[..., K]: both sides admit some value OUTSIDE the vocabulary."""
    both_other = _other_slot(a.mask, v) & _other_slot(b.mask, v)
    gt = jnp.maximum(a.gt, b.gt)
    lt = jnp.minimum(a.lt, b.lt)
    return both_other & _unseen_range_overlap(gt, lt, vocab_ints)


def nonempty_intersection(
    a: ReqTensor, b: ReqTensor, vocab_ints: Optional[jnp.ndarray] = None,
    v: Optional[int] = None,
) -> jnp.ndarray:
    """bool[..., K]: per-key Intersection(a, b).Len() > 0."""
    if is_packed(a):
        vw = jnp.asarray(vocab_words(v))
        vocab_overlap = jnp.any((a.mask & b.mask & vw) != 0, axis=-1)
    else:
        vocab_overlap = jnp.any(a.mask[..., :-1] & b.mask[..., :-1], axis=-1)
    return vocab_overlap | _unseen_overlap(a, b, vocab_ints, v)


def derive_negative(
    mask: jnp.ndarray,
    gt: jnp.ndarray,
    lt: jnp.ndarray,
    valid: jnp.ndarray,
    vocab_ints: Optional[jnp.ndarray],
    v: Optional[int] = None,
    key_has_bounds=None,
) -> jnp.ndarray:
    """bool[..., K]: operator ∈ {NotIn, DoesNotExist} for a mask-form set.

    Mirrors requirement.go:186-197 Operator(): a complement is NotIn iff its
    exclusion list is non-empty — and bounds drop out-of-range values from the
    exclusion list (requirement.go:139-143), so only *within-bounds* vocabulary
    values count as exclusions.  A concrete empty set is DoesNotExist.

    Packed layout (``mask``/``valid`` uint32 words): the bounds correction
    needs per-slot range tests, so it unpacks the (rare) exclusion words —
    skipped entirely when ``key_has_bounds`` (static per-key tuple) says no
    key carries Gt/Lt anywhere in the problem, the common case.
    """
    if mask.dtype == jnp.uint32:
        vw = jnp.asarray(vocab_words(v))
        excl_words = valid & ~mask & vw
        exclusions = jnp.any(excl_words != 0, axis=-1)
        needs_bounds = vocab_ints is not None and (
            key_has_bounds is None or any(key_has_bounds)
        )
        if needs_bounds:
            bounds_set = jnp.isfinite(gt) | jnp.isfinite(lt)
            in_range = (vocab_ints > gt[..., None]) & (vocab_ints < lt[..., None])
            excl_bits = unpack_mask(excl_words, v)[..., : v - 1]
            excl_bounded = jnp.any(excl_bits & in_range, axis=-1)
            exclusions = jnp.where(bounds_set, excl_bounded, exclusions)
        empty = ~jnp.any(mask != 0, axis=-1)
        return (other_bit(mask, v) & exclusions) | empty
    bounds_set = jnp.isfinite(gt) | jnp.isfinite(lt)
    if vocab_ints is None:
        within = jnp.ones(valid.shape[:-1] + (valid.shape[-1] - 1,), dtype=bool)
    else:
        in_range = (vocab_ints > gt[..., None]) & (vocab_ints < lt[..., None])
        within = jnp.where(bounds_set[..., None], in_range, True)
    exclusions = jnp.any(valid[..., :-1] & ~mask[..., :-1] & within, axis=-1)
    empty = ~jnp.any(mask, axis=-1)
    return (mask[..., -1] & exclusions) | empty


def intersection(
    a: ReqTensor,
    b: ReqTensor,
    valid: Optional[jnp.ndarray] = None,
    vocab_ints: Optional[jnp.ndarray] = None,
    v: Optional[int] = None,
    key_has_bounds=None,
) -> ReqTensor:
    """Key-wise intersection (requirement.go:117-150 under the mask encoding).

    Bound filtering of vocabulary values is already baked into each side's
    mask; combined bounds propagate by max/min.  Operator negativity is
    re-derived from the result (see derive_negative) when ``valid`` is given;
    the fallback (both-negative | empty) is exact except for complements whose
    exclusion lists change NotIn↔Exists across the intersection.
    """
    mask = a.mask & b.mask
    defined = a.defined | b.defined
    gt = jnp.maximum(a.gt, b.gt)
    lt = jnp.minimum(a.lt, b.lt)
    if valid is not None:
        negative = derive_negative(mask, gt, lt, valid, vocab_ints, v, key_has_bounds)
    else:
        if mask.dtype == jnp.uint32:
            empty = ~jnp.any(mask != 0, axis=-1)
        else:
            empty = ~jnp.any(mask, axis=-1)
        negative = (a.negative & b.negative) | empty
    return ReqTensor(mask, defined, negative, gt, lt)


def intersects(
    a: ReqTensor, b: ReqTensor, vocab_ints: Optional[jnp.ndarray] = None,
    v: Optional[int] = None,
) -> jnp.ndarray:
    """bool[...]: requirements.go:189-206 Intersects == nil.

    Only keys defined on BOTH sides are checked; an empty intersection is
    forgiven when both operators are negative (NotIn/DoesNotExist).
    """
    checked = a.defined & b.defined
    nonempty = nonempty_intersection(a, b, vocab_ints, v)
    both_negative = a.negative & b.negative
    key_ok = ~checked | nonempty | both_negative
    return jnp.all(key_ok, axis=-1)


def compatible(
    a: ReqTensor,
    b: ReqTensor,
    is_custom: jnp.ndarray,
    vocab_ints: Optional[jnp.ndarray] = None,
    v: Optional[int] = None,
) -> jnp.ndarray:
    """bool[...]: requirements.go:123-133 Compatible == nil, a=node side,
    b=incoming (pod) side.

    Adds the custom-label rule to Intersects: a custom (non-well-known) key
    required positively (In/Exists/Gt/Lt) by ``b`` must be defined on ``a``.
    ``is_custom`` is bool[K] from the vocabulary.
    """
    denied = is_custom & b.defined & ~b.negative & ~a.defined
    return intersects(a, b, vocab_ints, v) & ~jnp.any(denied, axis=-1)


def add(
    a: ReqTensor,
    b: ReqTensor,
    valid: Optional[jnp.ndarray] = None,
    vocab_ints: Optional[jnp.ndarray] = None,
    v: Optional[int] = None,
    key_has_bounds=None,
) -> ReqTensor:
    """Requirements.Add: a tightened by b (intersect-on-add per key,
    requirements.go:87-94)."""
    return intersection(a, b, valid, vocab_ints, v, key_has_bounds)


def count_allowed(
    a: ReqTensor, valid: jnp.ndarray, v: Optional[int] = None
) -> jnp.ndarray:
    """int32[..., K]: number of in-vocabulary values allowed per key.  The
    "other" slot is excluded — callers needing Len()-infinite semantics should
    test the other slot directly."""
    if is_packed(a):
        import jax

        vw = jnp.asarray(vocab_words(v))
        return jnp.sum(
            jax.lax.population_count(a.mask & valid & vw), axis=-1
        ).astype(jnp.int32)
    return jnp.sum((a.mask & valid).astype(jnp.int32)[..., :-1], axis=-1)


def single_value(a: ReqTensor, v: Optional[int] = None) -> jnp.ndarray:
    """bool[..., K]: the key collapsed to exactly one in-vocab value and
    excludes unseen values — the condition under which topology Record counts
    a domain (topology.go:129-131)."""
    if is_packed(a):
        import jax

        vw = jnp.asarray(vocab_words(v))
        in_vocab = jnp.sum(
            jax.lax.population_count(a.mask & vw), axis=-1
        ).astype(jnp.int32)
        return (in_vocab == 1) & ~other_bit(a.mask, v)
    in_vocab = jnp.sum(a.mask[..., :-1].astype(jnp.int32), axis=-1)
    return (in_vocab == 1) & ~a.mask[..., -1]
