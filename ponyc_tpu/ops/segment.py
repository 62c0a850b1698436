"""Sorted-segment primitives used by batched message delivery.

The delivery problem (scatter-append K messages into N ring buffers while
preserving per-sender order and respecting capacity) is solved the
XLA-friendly way: stable sort by target, compute each entry's *rank within
its target segment* with a prefix max, then one scatter. These helpers are
shared by single-chip delivery and the per-shard delivery inside shard_map.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def stable_sort_carrying(keys: jnp.ndarray, *payloads: jnp.ndarray):
    """(sorted keys, *payloads in the keys' order) of ONE stable
    ascending sort of int32 keys. Reading a payload back as
    `payload[perm]` is a gather, ~6-20 ns an index on a v5e; the sort
    that carries it costs ~1-2 ns a key and operand."""
    return jax.lax.sort((keys, *payloads), num_keys=1, is_stable=True)


def stable_sort_with_keys(keys: jnp.ndarray):
    """(sorted keys, permutation) of a stable ascending sort of int32
    keys: what `argsort` computes and then drops."""
    return stable_sort_carrying(
        keys, jax.lax.iota(jnp.int32, keys.shape[0]))


def segment_bounds(sorted_keys: jnp.ndarray, num_segments: int,
                   stride: int = 1) -> jnp.ndarray:
    """bounds[t] = how many of the ascending int32 `sorted_keys` (each
    in [0, num_segments * stride]) are below t * stride, t = 0 ..
    num_segments: `searchsorted(sorted_keys, arange(n + 1) * stride,
    side="left")`, computed as a MERGE of the two sorted sequences.

    A binary search is ~log2(E) dependent indexed reads a query, and an
    indexed read is the costliest thing a v5e does (24M of them for 1M
    queries into 8.4M keys: 167 ms). A sort is its cheapest data-
    dependent move, so the merge is two one-operand sorts and a prefix
    sum (~18 ms, same sizes): sort queries and keys together, the flag
    "is a key" in the low bit so that a query lands before the keys
    equal to it; a query's bound is then the number of keys before it;
    and since those counts rise with the query, sorting them (every
    key's entry pushed to the maximum) brings them to the front in
    query order. uint32 holds 2 * key + 1 for every int32 key."""
    n = num_segments
    queries = jnp.arange(n + 1, dtype=jnp.uint32) * jnp.uint32(2 * stride)
    merged = jax.lax.sort(jnp.concatenate(
        [queries, sorted_keys.astype(jnp.uint32) * 2 + 1]), is_stable=False)
    is_key = (merged & 1).astype(jnp.int32)
    before = jnp.cumsum(is_key) - is_key
    return jax.lax.sort(
        jnp.where(is_key == 1, jnp.iinfo(jnp.int32).max, before),
        is_stable=False)[:n + 1]


# A list of at most 1/SCATTER_BELOW of the id space is marked by a
# scatter (its serial updates cost less than sorting the id space).
SCATTER_BELOW = 16


def marks_of(ids, n: int):
    """[n] bool: which of 0..n-1 occur in `ids` (any shape; anything
    outside [0, n) is no id). Long lists by sort and merge, no scatter
    (runtime/gc.py's docstring has the rates)."""
    ids = ids.reshape(-1)
    if ids.shape[0] * SCATTER_BELOW <= n:
        return jnp.zeros((n,), jnp.bool_).at[
            jnp.where(ids >= 0, ids, n)].max(True, mode="drop")
    key = jnp.where((ids >= 0) & (ids < n), ids, n).astype(jnp.int32)
    # the merge sorts ids and keys together: it asks no order of the keys
    below = segment_bounds(key, n)                        # [n + 1]
    return below[1:] > below[:-1]


def segment_ranks(sorted_keys: jnp.ndarray) -> jnp.ndarray:
    """Given keys already sorted ascending, return each element's index
    within its run of equal keys. [3,3,5,5,5,9] → [0,1,0,1,2,0]."""
    n = sorted_keys.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    is_start = jnp.concatenate(
        [jnp.ones((1,), jnp.bool_), sorted_keys[1:] != sorted_keys[:-1]])
    seg_start = jax.lax.associative_scan(
        jnp.maximum, jnp.where(is_start, idx, 0))
    return idx - seg_start


def counts_by_key(keys: jnp.ndarray, weights: jnp.ndarray,
                  num_buckets: int) -> jnp.ndarray:
    """Scatter-add weights into num_buckets by key; out-of-range keys drop."""
    out = jnp.zeros((num_buckets,), weights.dtype)
    return out.at[keys].add(weights, mode="drop")


def compact_mask(mask: jnp.ndarray, cap: int):
    """Stable-compact True entries to the front, truncated/padded to cap.

    Returns (perm[cap], valid[cap], total_true). perm indexes the original
    array; entries beyond total_true are padding (valid=False). Order of the
    selected entries is preserved (stable sort on ~mask).
    """
    total = jnp.sum(mask.astype(jnp.int32))
    perm = jnp.argsort(~mask, stable=True)
    perm = perm[:cap]
    if perm.shape[0] < cap:         # a mask shorter than cap: padding
        perm = jnp.pad(perm, (0, cap - perm.shape[0]))
    valid = jnp.arange(cap, dtype=jnp.int32) < total
    return perm, valid, total
