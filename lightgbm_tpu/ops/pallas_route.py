"""Pallas TPU row routing: a split phase's committed splits in one pass.

A committed split sends the rows of one leaf that go right to a new leaf.
Until PR 35 every split was an XLA walk of its own over every row the chip
holds: two loop fusions (``pred[N]`` out and back, ``leaf_id`` in and out)
reading one bin column out of ``u8 [F, N]``, whose tiles interleave four
columns a 32-bit word and eight a sublane group: 0.066 ns a walked row on
the v5e, 54 bytes a row at the HBM peak where the walk needs nine, 254 times
a tree (ledger, PR 34; PERF.md 6, PR 35).

Here ``leaf_id`` crosses HBM once a PHASE.  A grid step holds a block of
``leaf_id`` lines (128 rows a line) in VMEM and loops over the phase's
committed slots; slot ``s`` brings its one physical column's block in by a
manual, double-buffered DMA out of a column-contiguous view of the bins
(``column_view``: ``[F, G, 128]``, a column's rows are whole tiles) and
rewrites the block in place.  What a slot decides on is scalars in SMEM,
gathered by the split's feature before the call; the decision itself is
``core/splitter.py split_decision`` and ``core/grower.py decode_feature_col``
called on the block, the slot's scalar facts choosing the branch so that a
numeric split without missing values pays two compares and a select.

On the v5e (PERF.md 6, PR 35): 0.22 ms a pass and 0.032 ms a plain slot at
10.5M rows (0.09 a slot with missing values, 0.19 a 16-word bitset slot on
a ``u16`` view, 0.075 a one-word one); a HIGGS tree's 254 splits in 13-15
passes cost 12 ms an iteration where the walks cost 177.
"""
from __future__ import annotations

import functools
import types

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.grower import decode_feature_col
from ..core.splitter import split_decision
from ..io.binning import MISSING_NONE

_LINE = 128         # rows a line of the kernel's [G, 128] blocks
_LINES = 2048       # lines a grid step: 262,144 rows, 256 KB a u8 column block
#   (a plain slot at 10.5M rows: 0.074 ms at 512 lines, 0.032 here)
_TILE = 32          # lines a u8 tile: a block is whole tiles of any dtype


def _block_lines(N: int) -> int:
    """Lines a grid step takes for ``N`` rows: ``_LINES``, or every line of
    a smaller table rounded up to whole tiles."""
    return min(_LINES, pl.cdiv(pl.cdiv(N, _LINE), _TILE) * _TILE)


def column_view(bins_fm, order=None):
    """The feature-major bins ``[F, N]`` as ``[F, G, 128]``, rows padded with
    zeros to whole blocks: one column's rows are then contiguous tiles, where
    ``[F, N]`` tiles 32 columns x 128 rows.  A relayout copy of the bins, made
    once a tree.  Given the mixed layout's ``(narrow, wide)`` pair, ONE view
    in the wider dtype whose row ``p`` is ``concat(narrow, wide)[order[p]]``
    (``order``: static, physical column -> its place in the pair)."""
    if isinstance(bins_fm, (tuple, list)):
        narrow, wide = bins_fm
        bins_fm = jnp.take(jnp.concatenate([narrow.astype(wide.dtype), wide]),
                           order, axis=0)
    F, N = bins_fm.shape
    RB = _block_lines(N)
    G = pl.cdiv(N, RB * _LINE) * RB
    return jnp.pad(bins_fm, ((0, 0), (0, G * _LINE - N))).reshape(F, G, _LINE)


def _route_kernel(*refs, names, W: int, bundled: bool, RB: int):
    n_ref, slot = refs[0], dict(zip(names, refs[1:1 + len(names)]))
    lid_ref, bins_hbm, out_ref, colbuf, sem = refs[1 + len(names):]
    i, n = pl.program_id(0), n_ref[0]
    # the slots' own facts where the decision's helpers look a feature's up
    slot_meta = types.SimpleNamespace(
        feat_offset=slot.get("feat_offset"), num_bins=slot["num_bins"],
        default_bins=slot["default_bins"])

    def copy(s, k):
        return pltpu.make_async_copy(
            bins_hbm.at[slot["phys"][s], pl.ds(i * RB, RB), :],
            colbuf.at[k], sem.at[k])

    def bitset_word(col, s, nw: int):
        """The slot's bitset word of each row's bin, of the first ``nw``:
        dense selects on ``col // 32`` (bins past the last word read it, as
        the XLA walk's clip does)."""
        words = slot["cat_bitset"]
        idx = col >> 5
        out = jnp.full(col.shape, words[s * W + nw - 1], jnp.int32)
        for w in range(nw - 1):
            out = jnp.where(idx == w, words[s * W + w], out)
        return jax.lax.bitcast_convert_type(out, jnp.uint32)

    def apply(s, k, is_cat: bool, plain: bool, nw: int = 0):
        """Slot ``s`` applied to the block.  ``is_cat`` / ``plain`` (numeric,
        no missing values) / ``nw`` (bitset words the feature's bins span)
        are static: each is one compiled branch, chosen below by the slot's
        scalars."""
        def run():
            col = colbuf[k].astype(jnp.int32)
            if bundled:
                col = decode_feature_col(col, s, slot_meta)
            go_left = split_decision(
                col, slot["threshold"][s], slot["default_left"][s] != 0,
                is_cat, bitset_word(col, s, nw) if is_cat else jnp.uint32(0),
                MISSING_NONE if plain or is_cat else slot["missing"][s],
                slot["num_bins"][s], slot["default_bins"][s])
            lid = out_ref[...]
            out_ref[...] = jnp.where((lid == slot["leaf"][s]) & ~go_left,
                                     slot["new"][s], lid)
        return run

    out_ref[...] = lid_ref[...]

    @pl.when(n > 0)
    def _first():
        copy(0, 0).start()

    def body(s, carry):
        k = s % 2
        copy(s, k).wait()

        @pl.when(s + 1 < n)
        def _next():
            copy(s + 1, 1 - k).start()

        def numeric():
            jax.lax.cond(slot["missing"][s] == MISSING_NONE,
                         apply(s, k, False, True), apply(s, k, False, False))
        def categorical():  # a feature of at most 32 bins reads one word
            jax.lax.cond(slot["num_bins"][s] <= 32,
                         apply(s, k, True, False, 1),
                         apply(s, k, True, False, W))
        if W:
            jax.lax.cond(slot["is_cat"][s] != 0, categorical, numeric)
        else:
            numeric()
        return carry

    jax.lax.fori_loop(0, n, body, 0)


@functools.partial(jax.jit, static_argnames=("bundled", "interpret"))
def route_rows(leaf_id, view, n, slots: dict, cat_bitset=None, *,
               bundled: bool = False, interpret: bool = False):
    """``leaf_id`` (i32 ``[N]``) after the first ``n`` slots' splits, by one
    pass over the rows.  ``view``: ``column_view`` of the bins.  ``slots``:
    i32 ``[P]`` each, the split's own ``leaf``, ``new``, ``threshold``,
    ``default_left`` and, looked up by its feature, ``phys`` (the view's
    row), ``missing``, ``num_bins``, ``default_bins``, under ``bundled`` also
    ``feat_offset``.  ``cat_bitset`` (u32 ``[P, W]``) with ``slots["is_cat"]``
    where the table has a categorical column; without it the bitset branch
    is not compiled.  A leaf splits at most once among the slots and no slot
    splits a child another created, so their order is immaterial."""
    N = leaf_id.shape[0]
    G = view.shape[1]
    RB = _block_lines(N)
    assert G % RB == 0 and G * _LINE >= N and view.shape[2] == _LINE
    W = 0
    if cat_bitset is not None:
        W = cat_bitset.shape[1]
        slots = dict(slots, cat_bitset=jax.lax.bitcast_convert_type(
            cat_bitset, jnp.int32).reshape(-1))
    names = tuple(sorted(slots))
    line = pl.BlockSpec((RB, _LINE), lambda i, *_: (i, 0),
                        memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        functools.partial(_route_kernel, names=names, W=W, bundled=bundled,
                          RB=RB),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1 + len(names), grid=(G // RB,),
            in_specs=[line, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=line,
            scratch_shapes=[pltpu.VMEM((2, RB, _LINE), view.dtype),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((G, _LINE), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        name="route_rows", interpret=interpret,
    )(jnp.reshape(n, (1,)).astype(jnp.int32),
      *(slots[k].astype(jnp.int32) for k in names),
      jnp.pad(leaf_id, (0, G * _LINE - N)).reshape(G, _LINE), view)
    return out.reshape(-1)[:N]
