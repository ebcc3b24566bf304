"""Device-side tree traversal over binned data.

Replaces the reference's training-time ``Tree::AddPredictionToScore`` inner
traversal (reference: include/LightGBM/tree.h:101-114, src/io/tree.cpp) with
a vectorized gather loop: every row walks the tree simultaneously, one level
per ``while_loop`` step, until all rows rest in leaves.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .grower import TreeArrays, decode_feature_col
from .meta import DeviceMeta
from .splitter import split_decision


@jax.named_scope("lgbm/tree_traverse")
def predict_leaf_bins(tree: TreeArrays, bins, meta: DeviceMeta,
                      phys: bool = False):
    """Leaf index per row for binned inputs. bins: [N, F] uint8/int32.

    ``phys=True`` reads EFB physical-column layout (training/valid bins of a
    bundled dataset) and decodes each node's feature bin on the fly;
    ``phys=False`` expects per-feature (inner) columns."""
    N = bins.shape[0]
    start = jnp.where(tree.num_leaves > 1, 0, ~0)
    node = jnp.full((N,), start, jnp.int32)

    def cond(node):
        return jnp.any(node >= 0)

    def step(node):
        active = node >= 0
        nd = jnp.maximum(node, 0)
        f = tree.split_feature[nd]
        fcol = meta.feat2phys[f] if phys else f
        col = jnp.take_along_axis(bins, fcol[:, None].astype(jnp.int32),
                                  axis=1)[:, 0].astype(jnp.int32)
        if phys:
            col = decode_feature_col(col, f, meta)
        # categorical nodes: membership in the node's bin-space bitset
        # (reference: Tree::CategoricalDecisionInner, tree.h:265-303) —
        # the word holding col's bit is gathered per row, then the shared
        # split_decision helper routes numerical/missing/categorical alike
        word = jnp.take_along_axis(tree.cat_bitset[nd],
                                   (col // 32)[:, None], axis=1)[:, 0]
        gl = split_decision(col, tree.threshold_bin[nd],
                            tree.default_left[nd], meta.is_categorical[f],
                            word, meta.missing_types[f], meta.num_bins[f],
                            meta.default_bins[f])
        nxt = jnp.where(gl, tree.left_child[nd], tree.right_child[nd])
        return jnp.where(active, nxt, node)

    node = jax.lax.while_loop(cond, step, node)
    return ~node


# Leaves above which ``leaf_value_lookup`` keeps the gather: the select tree
# costs by leaves x rows (and its program by leaves), the gather by rows alone
# (PERF.md 6, PR 39: step 0's readings on the chip, and what a tree of 255 to
# 4,096 leaves takes to compile).
DENSE_LOOKUP_MAX_LEAVES = 1024


def leaf_value_lookup(leaf_value, leaf_id):
    """``leaf_value[leaf_id]`` (f32 ``[L]``, i32 ``[N]`` -> f32 ``[N]``), to
    the bit, without a gather: up to ``DENSE_LOOKUP_MAX_LEAVES`` leaves (a
    static shape) a binary tree of selects over the id's bits, the leaves'
    values as scalars at its feet: bit 0 picks within each pair of leaves,
    bit 1 within each pair of pairs, ``L - 1`` dense selects a row in all,
    which XLA fuses into one streamed pass over the rows.  On the chip an
    N-row gather out of a table of more than 64 entries costs 8 ns a row
    whatever the table, this 0.15 ns at 255 leaves (PERF.md 6, PR 39).
    The selects move bit patterns, so ``-0.0``, infinities and denormals
    come out as they went in, and nothing can be contracted into the add
    that follows (XLA:CPU makes one FMA of ``leaf_value * lr``, the gather
    and the add: an ulp off the exported leaf value).

    Needs what the gather did not: **0 <= leaf_id < L on every row** (the
    gather clamps an id out of range; the tree reads the leaf its low bits
    name).  Every producer keeps it: both growers start ``leaf_id`` at zeros
    and only ever write a committed split's ``new``, the tree's
    ``num_leaves`` before the split, below ``L`` (``core/grower.py``,
    ``core/wave_grower.py _commit_split_meta``); out-of-bag rows and GOSS's
    unsampled ones carry weight 0 and are routed like any other; the mesh
    pads the row vectors with weight 0, its padded rows start at 0 and
    follow splits like the rest, and ``leaf_id[:N]`` cuts them off
    (``parallel/mesh.py make_engine_grower``); ``predict_leaf_bins`` ends
    at ``~node`` of a leaf's encoded child, 0 for a tree that never grew
    (``tests/test_score_lookup.py`` holds each)."""
    L = leaf_value.shape[0]
    if L > DENSE_LOOKUP_MAX_LEAVES:
        return leaf_value[leaf_id]
    bits = jax.lax.bitcast_convert_type(leaf_value, jnp.int32)
    vals = [bits[i] for i in range(L)]
    shift = 0
    while len(vals) > 1:
        odd = ((leaf_id >> shift) & 1) == 1
        pairs = [jnp.where(odd, hi, lo)
                 for lo, hi in zip(vals[0::2], vals[1::2])]
        vals = pairs + vals[2 * len(pairs):]    # an unpaired last rides up
        shift += 1
    return jax.lax.bitcast_convert_type(
        jnp.broadcast_to(vals[0], leaf_id.shape), jnp.float32)
