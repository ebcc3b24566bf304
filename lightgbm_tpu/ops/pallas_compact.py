"""Pallas TPU stream compaction: a tier's rows by one sequential pass.

A wave below the full tier histograms only the rows of its pending leaves.
Until PR 31 they reached the front of the tier by three per-row gathers out
of HBM (the bins row, ``leaf_id``, the row vectors) through an index built
for the purpose: 55-70 ns a tier row on the v5e, half of a HIGGS iteration
(PERF.md 5 and 6, PR 29 and 31).  A gather on the chip goes by its output
rows and by latency; a streamed row costs a two-hundredth of that.  So this
kernel streams EVERY row the chip holds once and writes the active ones, in
row order, to the front of the output, feature-major as the histogram
kernel reads them.

Placement is a lane permutation of 32-bit words, on the vector unit.  A
row's payload travels as words whatever the bits mean: the bins four
feature rows a word (``pltpu.bitcast`` of the ``u8`` block), the three row
vectors' bits and ``leaf_id`` a word row each, the mixed layout's ``u16``
columns two a word.  For a grid step's sub-blocks of 128 rows at once, the
rank of every row among its sub-block's active ones (one triangular matmul
of the mask) is turned into SOURCE LANES by a 7-stage compress network
(``compress_lanes``), and one gather turns every line by the lane its
sub-block starts at in the staging buffer.  A sub-block is then placed by
one lane gather a payload vreg (``jnp.take_along_axis`` along lanes:
Mosaic's ``tpu.dynamic_gather``) and two stores, without a branch: what it
writes past its own count the next sub-block writes over, so one with no
active row costs what any other does and the cost does not move with the
active share.  The staging buffer (VMEM scratch carried across the
sequential grid) is flushed to HBM a lane-aligned block at a time, the
bins back as ``u8``, so nothing is ever sliced at an unaligned dynamic
offset and nothing is converted after the kernel.

On the v5e (PERF.md 6, PR 37's step 0): 0.22-0.23 ns a streamed row at
6-28 columns and 0.29-0.38 at 136, whatever share of the rows is active,
bit for bit what the placement matmul of PR 31 wrote at 1.3-1.6 and 2.3
(0.7 and 1.2 where 0.5% were active).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_SUB = 128          # rows a sub-block: one lane tile, one permutation
_GROUP = 16         # sub-blocks placed between two looks at the flush
_SCALARS = 1024     # i32 a block of an SMEM operand: XLA tiles them so
_MIB = 2 ** 20
_LEAF_ROW = 3       # g, h, c are word rows 0-2; leaf_id's line is row 3


def word_rows(x):
    """An array ``[..., N]`` of 1-, 2- or 4-byte elements as 32-bit words,
    ``i32 [ceil(R k / 4), N]`` (``R`` the product of the leading axes, ``k``
    the element's bytes): 4 / k consecutive rows a word, the first in the
    low bits, zero rows where ``R`` does not fill the last word."""
    k = x.dtype.itemsize
    x = x.reshape(-1, x.shape[-1])
    if k == 4:
        return jax.lax.bitcast_convert_type(x, jnp.int32)
    per = 4 // k
    u = jax.lax.bitcast_convert_type(x, jnp.dtype(f"uint{8 * k}"))
    u = jnp.pad(u, ((0, -x.shape[0] % per), (0, 0))).astype(jnp.uint32)
    u = u.reshape(-1, per, x.shape[-1])
    w = u[:, 0]
    for i in range(1, per):
        w = w | (u[:, i] << (8 * k * i))
    return jax.lax.bitcast_convert_type(w, jnp.int32)


def rows_value(w, dtype, rows: int):
    """``word_rows`` back: ``i32 [ceil(rows k / 4), T]`` as ``dtype [rows,
    T]``, bit for bit."""
    dtype = jnp.dtype(dtype)
    k = dtype.itemsize
    if k == 4:
        return jax.lax.bitcast_convert_type(w, dtype)
    per = 4 // k
    u = jax.lax.bitcast_convert_type(w, jnp.uint32)
    parts = jnp.stack([u >> (8 * k * i) for i in range(per)], axis=1)
    parts = parts.astype(jnp.dtype(f"uint{8 * k}")).reshape(-1, w.shape[-1])
    return jax.lax.bitcast_convert_type(parts[:rows], dtype)


def row_words(g, h, c, wide=None):
    """What a tree's waves stream beside the bins, built once a tree: ``i32
    [E, N]``, rows 0-2 the three row vectors' bits (f32 ``[N]`` each), row
    3 zero (the kernel lays the wave's ``leaf_id`` there), then the words
    of the mixed layout's ``wide`` columns (``[Fw, N]``), zero rows up to a
    multiple of 8."""
    N = g.shape[0]
    parts = [word_rows(v[None]) for v in (g, h, c)]
    parts.append(jnp.zeros((1, N), jnp.int32))
    if wide is not None:
        parts.append(word_rows(wide))
    rows = sum(p.shape[0] for p in parts)
    if rows % 8:
        parts.append(jnp.zeros((-rows % 8, N), jnp.int32))
    return jnp.concatenate(parts)


def compress_lanes(m):
    """``src i32 [S, 128]`` of the mask lines ``m`` (bf16 ``[S, 128]``, 1.0
    where a row is active): lane ``j`` of line ``s`` holds the lane of the
    j-th active row of the line, for ``j`` below the line's count (the
    other lanes hold some lane number).  The rank of a row among its
    line's active ones is one triangular matmul; the ranks then become
    source lanes by a 7-stage compress network over the lines: a lane
    whose displacement ``lane - rank`` has bit ``k`` set moves left by
    ``2^k``, least significant bit first (order is kept and the moves of a
    stage never collide)."""
    S = m.shape[0]
    tri = (jax.lax.broadcasted_iota(jnp.int32, (_SUB, _SUB), 0)
           < jax.lax.broadcasted_iota(jnp.int32, (_SUB, _SUB), 1))
    rank = jnp.dot(m, tri.astype(jnp.float32).astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32).astype(jnp.int32)
    lane = jax.lax.broadcasted_iota(jnp.int32, (S, _SUB), 1)
    act = m.astype(jnp.float32) > 0     # no compare of bf16 on the v5e
    d = jnp.where(act, lane - rank, 0)
    idx = lane
    for k in range(7):
        sh = 1 << k                             # roll by 128 - sh: left by sh
        m_idx = pltpu.roll(idx, _SUB - sh, axis=1)
        m_d = pltpu.roll(d, _SUB - sh, axis=1)
        m_act = pltpu.roll(act.astype(jnp.int32), _SUB - sh, axis=1) > 0
        take = m_act & (lane < _SUB - sh) & (((m_d >> k) & 1) == 1)
        stay = act & (((d >> k) & 1) == 0)
        idx = jnp.where(take, m_idx, idx)
        d = jnp.where(take, m_d, d)
        act = take | stay
    return idx


def _rows_block(F: int) -> int:
    """Sublanes of the bins block: whole u8 tiles of 32, which bitcast to
    whole i32 tiles of 8 word rows (the block's rows past F read as garbage
    bytes and land in output rows nobody reads)."""
    return -(-F // 32) * 32


def _blocks(E: int, Fb: int):
    """``(sub-blocks a grid step, lanes a flush writes, VMEM bytes)`` for a
    payload of ``E`` word rows and ``Fb`` byte rows: 128 sub-blocks (16,384
    rows) and 4,096 lanes up to 220 bytes a row (every shape the benchmark
    runs), fewer of both where the payload is wider, so that the streamed
    blocks (double-buffered), the staging buffer and the output buffers
    stay in VMEM.  Never under a group of sub-blocks, nor under the lanes a
    group can add (the drain's two flushes empty the stage)."""
    D = 4 * E + Fb
    subs = 128
    while subs > _GROUP and 2 * D * subs * _SUB > 7 * _MIB:
        subs //= 2
    flush = 4096
    while flush > _GROUP * _SUB and D * flush * 2 > 2 * _MIB:
        flush //= 2
    need = 2 * (D + 8) * subs * _SUB + 2 * D * (flush + _GROUP * _SUB)
    return subs, flush, max(16 * _MIB, 2 * need)


def _compact_kernel(start_ref, mask_ref, leaf_ref, words_ref, bins_ref,
                    words_out, bins_out, stage, src, obuf_w, obuf_b,
                    flushed, sem, *, E: int, Fb: int, cap: int, subs: int,
                    flush_w: int):
    i = pl.program_id(0)
    Wb = Fb // 4
    tail = _GROUP * _SUB        # lanes the stage holds behind a flush

    @pl.when(i == 0)
    def _init():
        flushed[0] = 0

    def out_copies(nf):
        at = pl.ds(pl.multiple_of(nf * flush_w, flush_w), flush_w)
        return [pltpu.make_async_copy(obuf_w, words_out.at[:, at], sem.at[0]),
                pltpu.make_async_copy(obuf_b, bins_out.at[:, at], sem.at[1])]

    def flush():
        """The first flush_w lanes out to HBM at their aligned place; what
        is behind them moves to the front.  The copy out of the output
        buffers is waited for when they are filled again (and at the
        end)."""
        nf = flushed[0]

        @pl.when((nf + 1) * flush_w <= cap)
        def _write():
            @pl.when(nf > 0)
            def _():
                for cp in out_copies(nf - 1):
                    cp.wait()
            obuf_w[...] = stage[:E, :flush_w]
            obuf_b[...] = pltpu.bitcast(stage[E:, :flush_w], jnp.uint8)
            for cp in out_copies(nf):
                cp.start()
        stage[:, :tail] = stage[:, flush_w:]
        flushed[0] = nf + 1

    lane = jax.lax.broadcasted_iota(jnp.int32, (1, _SUB), 1)
    s0 = (i % (_SCALARS // subs)) * subs
    m = mask_ref[...]
    # the lane each sub-block starts at in its lane tile, for the grid step
    # at once: the lines' counts summed over the lines before (two
    # matmuls: counts <= 128 and their sums are exact), from the step's own
    # start; a flush moves whole lane tiles, so the lane is the start's low
    # bits whatever was flushed.  One gather then turns every line of
    # source lanes by its offset: lane ``off + j`` names the j-th active row
    tot = jnp.dot(m, jnp.ones((_SUB, _SUB), jnp.bfloat16),
                  preferred_element_type=jnp.float32)
    before = (jax.lax.broadcasted_iota(jnp.int32, (subs, subs), 0)
              > jax.lax.broadcasted_iota(jnp.int32, (subs, subs), 1))
    pre = jnp.dot(before.astype(jnp.float32).astype(jnp.bfloat16),
                  tot.astype(jnp.bfloat16),
                  preferred_element_type=jnp.float32).astype(jnp.int32)
    off_l = (start_ref[s0] + pre) & (_SUB - 1)
    src[...] = jnp.take_along_axis(
        compress_lanes(m), (lane - off_l) & (_SUB - 1), axis=1,
        mode="promise_in_bounds")

    is_leaf = jax.lax.broadcasted_iota(jnp.int32, (E, _SUB), 0) == _LEAF_ROW

    def place_group(gi, carry):
        k0 = gi * _GROUP

        # a group adds at most ``tail`` rows, which the stage holds behind
        # a flush: one look a group is enough, and a sub-block is placed
        # without a branch (one with no active row writes only lanes that
        # the next one writes over)
        @pl.when(start_ref[s0 + k0] - flushed[0] * flush_w >= flush_w)
        def _():
            flush()
        base = flushed[0] * flush_w
        for k in range(_GROUP):
            s = k0 + k
            lo = start_ref[s0 + s] - base
            a = pl.multiple_of((lo >> 7) << 7, _SUB)
            col = pl.ds(pl.multiple_of(s * _SUB, _SUB), _SUB)
            w = jnp.where(is_leaf, leaf_ref[pl.ds(s, 1), :], words_ref[:, col])
            b = pltpu.bitcast(bins_ref[:, col], jnp.int32)      # [Wb, 128]
            line = src[pl.ds(s, 1), :]                          # [1, 128]
            keep = lane >= (lo & (_SUB - 1))
            for x, r0, n in ((w, 0, E), (b, E, Wb)):
                y = jnp.take_along_axis(
                    x, jnp.broadcast_to(line, (n, _SUB)), axis=1,
                    mode="promise_in_bounds")
                # lanes [off, 128) of the tile the sub-block starts in,
                # and the lanes that wrapped into the next one (what lies
                # past the sub-block's count is written over by the next)
                rows = pl.ds(r0, n)
                head = pl.ds(a, _SUB)
                stage[rows, head] = jnp.where(keep, y, stage[rows, head])
                stage[rows, pl.ds(a + _SUB, _SUB)] = y
        return carry

    jax.lax.fori_loop(0, subs // _GROUP, place_group, 0)

    @pl.when(i == pl.num_programs(0) - 1)
    def _drain():
        flush()
        flush()
        for cp in out_copies(0):        # the last flush written
            cp.wait()


@functools.partial(jax.jit, static_argnames=("cap", "interpret"))
def stream_rows(bins_fm, words, leaf_id, active, start, n_active, cap: int,
                interpret: bool = False):
    """One wave's compaction.  The ``active`` rows (bool ``[N]``) of
    ``bins_fm`` (``u8 [F, N]``), of ``words`` (``row_words``: the same all
    tree long) and of ``leaf_id`` (i32 ``[N]``, this body's) in row order
    at the front of ``(bins_s u8 [Fb, W], words_s i32 [E, W])``, ``W`` being
    ``cap`` rounded up to a flush (4,096 lanes) and ``cap >= n_active``:
    column ``j < n_active`` is row ``np.flatnonzero(active)[j]`` bit for bit
    (rows ``[:F]`` of ``bins_s``; ``leaf_id`` in row 3 of ``words_s``).
    From ``n_active`` on, whatever the memory held: ``tier_front`` reads a
    tier's inputs off the pair.  ``start`` (i32 ``[G]``, the active rows
    before each word of 32 rows) and ``n_active`` are
    ``pack_active_rows``'."""
    F, N = bins_fm.shape
    E = words.shape[0]
    assert E % 8 == 0 and words.shape[1] == N and words.dtype == jnp.int32
    Fb = _rows_block(F)
    subs, flush_w, vmem = _blocks(E, Fb)
    W = -(-cap // flush_w) * flush_w
    RB = _SUB * subs
    nb = -(-N // RB)
    G = nb * subs

    def lines(v, dtype):        # [N] as the kernel's [G, 128] lines
        return jnp.pad(v, (0, G * _SUB - N)).reshape(G, _SUB).astype(dtype)
    # one a 128 rows (a reshape and a slice: start[::4] lowers to a gather);
    # a sub-block past the rows starts where the last one ends
    sub_start = start.reshape(-1, _SUB // 32)[:, 0]
    pad = -(-G // _SCALARS) * _SCALARS - sub_start.shape[0]
    scalars = pl.BlockSpec((_SCALARS,), lambda i: (i // (_SCALARS // subs),),
                           memory_space=pltpu.SMEM)
    line = pl.BlockSpec((subs, _SUB), lambda i: (i, 0),
                        memory_space=pltpu.VMEM)
    words_s, bins_s = pl.pallas_call(
        functools.partial(_compact_kernel, E=E, Fb=Fb, cap=W, subs=subs,
                          flush_w=flush_w),
        grid=(nb,),
        in_specs=[
            scalars, line, line,
            pl.BlockSpec((E, RB), lambda i: (0, i), memory_space=pltpu.VMEM),
            pl.BlockSpec((Fb, RB), lambda i: (0, i), memory_space=pltpu.VMEM),
        ],
        out_specs=[pl.BlockSpec(memory_space=pl.ANY),
                   pl.BlockSpec(memory_space=pl.ANY)],
        out_shape=[jax.ShapeDtypeStruct((E, W), jnp.int32),
                   jax.ShapeDtypeStruct((Fb, W), jnp.uint8)],
        scratch_shapes=[
            pltpu.VMEM((E + Fb // 4, flush_w + _GROUP * _SUB), jnp.int32),
            pltpu.VMEM((subs, _SUB), jnp.int32),
            pltpu.VMEM((E, flush_w), jnp.int32),
            pltpu.VMEM((Fb, flush_w), jnp.uint8),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=vmem),
        interpret=interpret,
    )(jnp.pad(sub_start, (0, pad), constant_values=n_active),
      lines(active, jnp.bfloat16), lines(leaf_id, jnp.int32), words, bins_fm)
    return bins_s, words_s


def tier_front(bins_s, words_s, n_active, T: int, F: int, wide=None):
    """The first ``T`` columns of ``stream_rows``' pair as the histogram
    kernel takes them: ``(bins_c u8 [F, T], g, h, c f32 [T], leaf_c i32
    [T], wide_c)``, by a slice, a bitcast and the ``live`` mask.  Column
    ``j < n_active`` is the j-th active row, bit for bit; from ``n_active``
    on ``leaf_c`` is -2, which misses every channel slot, and the vectors
    are zero (what the kernel did not write may hold any bits, and NaN x 0
    in a one-hot contraction is NaN).  ``wide``: ``(dtype, Fw)`` of the
    mixed layout's wide columns, else ``wide_c`` is None."""
    live = jnp.arange(T, dtype=jnp.int32) < n_active
    w = words_s[:, :T]
    g, h, c = (jnp.where(live, jax.lax.bitcast_convert_type(w[k],
                                                            jnp.float32), 0.0)
               for k in range(3))
    leaf_c = jnp.where(live, w[_LEAF_ROW], -2)
    wide_c = None
    if wide is not None:
        dtype, Fw = wide
        wide_c = rows_value(w[_LEAF_ROW + 1:], dtype, Fw)
    return bins_s[:F, :T], g, h, c, leaf_c, wide_c
