"""Pallas TPU stream compaction: a tier's rows by one sequential pass.

A wave below the full tier histograms only the rows of its pending leaves.
Until PR 31 they reached the front of the tier by three per-row gathers out
of HBM (the bins row, ``leaf_id``, the row vectors) through an index built
for the purpose: 55-70 ns a tier row on the v5e, half of a HIGGS iteration
(PERF.md 5 and 6, PR 29 and 31).  A gather on the chip goes by its output
rows and by latency; a streamed row costs a fortieth of that.  So this
kernel streams EVERY row the chip holds once and writes the active ones, in
row order, to the front of the output, feature-major as the histogram
kernel reads them.

Placement is a matmul.  Rows travel as byte lanes (``u8 [D, N]``: the bins
columns as they are, each 32-bit word of the row vectors and of ``leaf_id``
as four lanes), so every element is an integer <= 255, exact in bf16.  For
a sub-block of 128 rows the 0/1 placement matrix ``PT[j, i] = active_i &
(slot_i == j)`` is built by one iota compare, ``slot_i`` being the row's
rank among the sub-block's active rows (a triangular matmul of the mask,
one for all the sub-blocks of a grid step) plus where the sub-block starts
in the staging buffer; ``X[D, 128] @ PT^T`` then lands each active row in
its lane.  Every output element is one product of 1.0 and an integer <= 255
with f32 accumulation: the copy is bit-exact, whatever the bits mean.  The
staging buffer (VMEM scratch carried across the sequential grid) is flushed
to HBM a lane-aligned block at a time, so nothing is ever sliced at an
unaligned dynamic offset.  A sub-block with no active row is skipped on a
scalar test, so a sparse wave pays little more than the stream itself.

On the v5e (PERF.md 6, PR 31): 1.4-1.5 ns a streamed row at 16-28 columns
and 2.4 at 136 where 30% of the rows are active, 0.7 and 1.3 where 0.5%
are.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_SUB = 128          # rows a sub-block: one lane tile, one placement matrix
_SCALARS = 1024     # i32 a block of an SMEM operand: XLA tiles them so
_MIB = 2 ** 20
_VEC_PLANES = 12    # g, h, c: three f32 words a row
_LEAF_AT = _VEC_PLANES      # leaf_id's four lanes follow them


def byte_planes(x):
    """An array ``[..., N]`` of k-byte elements as its byte lanes, ``u8
    [k * prod(...), N]``, least significant first (lane ``b`` of element
    ``e`` of the leading axes is row ``b * prod(...) + e``): what the
    placement matmul carries exactly, whatever the bits mean."""
    k = x.dtype.itemsize
    if k == 1:
        return jax.lax.bitcast_convert_type(x, jnp.uint8).reshape(
            -1, x.shape[-1])
    u = jax.lax.bitcast_convert_type(x, jnp.dtype(f"uint{8 * k}"))
    return jnp.stack([((u >> (8 * b)) & 0xFF).astype(jnp.uint8)
                      for b in range(k)]).reshape(-1, x.shape[-1])


def planes_value(p, dtype, lead=()):
    """``byte_planes`` back: ``u8 [k * prod(lead), T]`` as ``dtype [*lead,
    T]``, bit for bit."""
    dtype = jnp.dtype(dtype)
    k = dtype.itemsize
    b = p.reshape((k,) + tuple(lead) + p.shape[-1:]).astype(
        jnp.dtype(f"uint{8 * k}"))
    u = b[0]
    for i in range(1, k):
        u = u | (b[i] << (8 * i))
    return jax.lax.bitcast_convert_type(u, dtype)


def row_planes(g, h, c, wide=None):
    """What a tree's waves stream beside the bins, built once a tree: ``u8
    [E, N]``, lanes 0-11 the three row vectors (f32 ``[N]`` each), lanes
    12-15 zero (the kernel lays the wave's ``leaf_id`` there), then the
    byte lanes of the mixed layout's ``wide`` columns (``[Fw, N]``), zero
    lanes up to a multiple of 16."""
    N = g.shape[0]
    parts = [byte_planes(v) for v in (g, h, c)]
    parts.append(jnp.zeros((4, N), jnp.uint8))
    if wide is not None:
        parts.append(byte_planes(wide))
    rows = sum(p.shape[0] for p in parts)
    if rows % 16:
        parts.append(jnp.zeros((-rows % 16, N), jnp.uint8))
    return jnp.concatenate(parts)


def _rows_block(F: int) -> int:
    """Sublanes of the bins block: F where the bf16 operand tiles as it is,
    else the next u8 tile (the block's rows past F read as garbage bytes and
    land in output rows nobody reads)."""
    return F if F % 16 == 0 else -(-F // 32) * 32


def _blocks(E: int, Fb: int):
    """``(sub-blocks a grid step, lanes a flush writes, VMEM bytes)`` for a
    payload of ``E + Fb`` byte lanes: 128 sub-blocks (16,384 rows) and
    4,096 lanes up to 176 lanes of payload (every shape the benchmark
    runs), fewer of both where the payload is wider, so that the streamed
    blocks (double-buffered) and the staging buffer stay in VMEM."""
    D = E + Fb
    subs = 128
    while subs > 16 and 2 * (Fb + max(E, 32)) * subs * _SUB > 7 * _MIB:
        subs //= 2
    flush = 4096
    while flush > 1024 and D * flush * 10 > 8 * _MIB:
        flush //= 2
    need = 2 * (Fb + max(E, 32)) * subs * _SUB + D * flush * 10
    return subs, flush, max(16 * _MIB, 2 * need)


def _compact_kernel(start_ref, cnt_ref, mask_ref, leaf_ref, planes_ref,
                    bins_ref, planes_out, bins_out, stage, slot, obuf_p,
                    obuf_b, flushed, sem, *, E: int, Fb: int, cap: int,
                    subs: int, flush_w: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        stage[...] = jnp.zeros_like(stage)
        flushed[0] = 0

    def flush():
        """The first flush_w lanes out to HBM at their aligned place; what
        is behind them moves to the front."""
        nf = flushed[0]

        @pl.when((nf + 1) * flush_w <= cap)
        def _write():
            v = stage[:, :flush_w].astype(jnp.int32)
            v = jnp.where(v > 127, v - 256, v).astype(jnp.int8)
            obuf_p[...] = v[:E]
            obuf_b[...] = v[E:]
            at = pl.ds(pl.multiple_of(nf * flush_w, flush_w), flush_w)
            copies = [
                pltpu.make_async_copy(obuf_p, planes_out.at[:, at],
                                      sem.at[0]),
                pltpu.make_async_copy(obuf_b, bins_out.at[:, at], sem.at[1])]
            for cp in copies:
                cp.start()
            for cp in copies:
                cp.wait()
        stage[:, :_SUB] = stage[:, flush_w:]
        stage[:, _SUB:] = jnp.zeros((E + Fb, flush_w), jnp.float32)
        flushed[0] = nf + 1

    # every sub-block's exclusive rank of its rows among its active ones,
    # in one matmul: mask [S, 128] @ strictly-upper-triangular [128, 128]
    m = mask_ref[...]
    tri = (jax.lax.broadcasted_iota(jnp.int32, (_SUB, _SUB), 0)
           < jax.lax.broadcasted_iota(jnp.int32, (_SUB, _SUB), 1))
    rank = jnp.dot(m, tri.astype(jnp.float32).astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32)
    slot[...] = jnp.where(m.astype(jnp.float32) > 0, rank.astype(jnp.int32),
                          -(1 << 20))

    lane_j = jax.lax.broadcasted_iota(jnp.int32, (2 * _SUB, _SUB), 0)
    plane = jax.lax.broadcasted_iota(jnp.int32, (E, _SUB), 0) - _LEAF_AT
    is_leaf = (plane >= 0) & (plane < 4)
    leaf_shift = jnp.clip(plane, 0, 3) * 8
    s0 = (i % (_SCALARS // subs)) * subs

    def sub(s, carry):
        cnt = cnt_ref[s0 + s]

        @pl.when(cnt > 0)
        def _place():
            st = start_ref[s0 + s]

            @pl.when(st - flushed[0] * flush_w >= flush_w)
            def _():
                flush()
            lo = st - flushed[0] * flush_w
            a = pl.multiple_of((lo // _SUB) * _SUB, _SUB)
            sl = slot[pl.ds(s, 1), :] + (lo - a)               # [1, 128]
            col = pl.ds(pl.multiple_of(s * _SUB, _SUB), _SUB)
            leaf = jnp.where(
                is_leaf, (leaf_ref[pl.ds(s, 1), :] >> leaf_shift) & 0xFF, 0)
            x = jnp.concatenate(
                [(planes_ref[:, col].astype(jnp.int32) + leaf).astype(
                    jnp.float32),
                 bins_ref[:, col].astype(jnp.int32).astype(jnp.float32)],
                axis=0).astype(jnp.bfloat16)                   # [D, 128]

            def place(tiles):
                """The sub-block's active rows into ``tiles`` lane tiles
                of the staging buffer from lane ``a`` on."""
                w = tiles * _SUB
                pt = (lane_j[:w] == sl).astype(jnp.float32).astype(
                    jnp.bfloat16)                              # [w, 128]
                y = jax.lax.dot_general(x, pt, (((1,), (1,)), ((), ())),
                                        preferred_element_type=jnp.float32)
                stage[:, pl.ds(a, w)] += y

            # most sub-blocks end inside the lane tile they start in
            # (nearly all where few rows are active): half the placement
            one = (lo - a) + cnt <= _SUB
            pl.when(one)(lambda: place(1))
            pl.when(jnp.logical_not(one))(lambda: place(2))
        return carry

    jax.lax.fori_loop(0, subs, sub, 0)

    @pl.when(i == pl.num_programs(0) - 1)
    def _drain():
        flush()
        flush()


@functools.partial(jax.jit, static_argnames=("cap", "interpret"))
def stream_rows(bins_fm, planes, leaf_id, active, start, n_active, cap: int,
                interpret: bool = False):
    """One wave's compaction.  The ``active`` rows (bool ``[N]``) of
    ``bins_fm`` (``u8 [F, N]``), of ``planes`` (``row_planes``: the same all
    tree long) and of ``leaf_id`` (i32 ``[N]``, this body's) in row order
    at the front of ``(bins_s u8 [Fb, W], planes_s u8 [E, W])``, ``W`` being
    ``cap`` rounded up to a flush (4,096 lanes) and ``cap >= n_active``:
    column ``j < n_active`` is row ``np.flatnonzero(active)[j]`` bit for bit
    (rows ``[:F]`` of ``bins_s``; ``leaf_id`` in lanes 12-15 of
    ``planes_s``).  From ``n_active`` on, zeros up to the next whole flush
    and whatever the memory held after it: ``tier_front`` reads a tier's inputs
    off the pair.  ``start`` (i32 ``[G]``, the active rows before each word
    of 32 rows) and ``n_active`` are ``pack_active_rows``'."""
    F, N = bins_fm.shape
    E = planes.shape[0]
    assert E % 16 == 0 and planes.shape[1] == N
    Fb = _rows_block(F)
    subs, flush_w, vmem = _blocks(E, Fb)
    W = -(-cap // flush_w) * flush_w
    RB = _SUB * subs
    nb = -(-N // RB)
    G = nb * subs

    def lines(v, dtype):        # [N] as the kernel's [G, 128] lines
        return jnp.pad(v, (0, G * _SUB - N)).reshape(G, _SUB).astype(dtype)
    # one a 128 rows (a reshape and a slice: start[::4] lowers to a gather)
    sub_start = start.reshape(-1, _SUB // 32)[:, 0]
    sub_end = jnp.concatenate([sub_start[1:], jnp.reshape(n_active, (1,))])
    pad = -(-G // _SCALARS) * _SCALARS - sub_start.shape[0]
    scalars = pl.BlockSpec((_SCALARS,), lambda i: (i // (_SCALARS // subs),),
                           memory_space=pltpu.SMEM)
    line = pl.BlockSpec((subs, _SUB), lambda i: (i, 0),
                        memory_space=pltpu.VMEM)
    planes_s, bins_s = pl.pallas_call(
        functools.partial(_compact_kernel, E=E, Fb=Fb, cap=W, subs=subs,
                          flush_w=flush_w),
        grid=(nb,),
        in_specs=[
            scalars, scalars, line, line,
            pl.BlockSpec((E, RB), lambda i: (0, i), memory_space=pltpu.VMEM),
            pl.BlockSpec((Fb, RB), lambda i: (0, i), memory_space=pltpu.VMEM),
        ],
        out_specs=[pl.BlockSpec(memory_space=pl.ANY),
                   pl.BlockSpec(memory_space=pl.ANY)],
        out_shape=[jax.ShapeDtypeStruct((E, W), jnp.int8),
                   jax.ShapeDtypeStruct((Fb, W), jnp.int8)],
        scratch_shapes=[
            pltpu.VMEM((E + Fb, flush_w + _SUB), jnp.float32),
            pltpu.VMEM((subs, _SUB), jnp.int32),
            pltpu.VMEM((E, flush_w), jnp.int8),
            pltpu.VMEM((Fb, flush_w), jnp.int8),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=vmem),
        interpret=interpret,
    )(jnp.pad(sub_start, (0, pad)), jnp.pad(sub_end - sub_start, (0, pad)),
      lines(active, jnp.bfloat16), lines(leaf_id, jnp.int32), planes, bins_fm)
    return (jax.lax.bitcast_convert_type(bins_s, jnp.uint8),
            jax.lax.bitcast_convert_type(planes_s, jnp.uint8))


def tier_front(bins_s, planes_s, n_active, T: int, F: int, wide=None):
    """The first ``T`` columns of ``stream_rows``' pair as the histogram
    kernel takes them: ``(bins_c u8 [F, T], g, h, c f32 [T], leaf_c i32
    [T], wide_c)``.  Column ``j < n_active`` is the j-th active row, bit for
    bit; from ``n_active`` on ``leaf_c`` is -2, which misses every channel
    slot, and the vectors are zero (what the kernel did not write may hold
    any bytes, and NaN x 0 in a one-hot contraction is NaN).  ``wide``:
    ``(dtype, Fw)`` of the mixed layout's wide columns, else ``wide_c`` is
    None."""
    live = jnp.arange(T, dtype=jnp.int32) < n_active
    p = planes_s[:, :T]
    g, h, c = (jnp.where(live, planes_value(p[4 * k:4 * k + 4],
                                            jnp.float32), 0.0)
               for k in range(3))
    leaf_c = jnp.where(live, planes_value(p[_LEAF_AT:_LEAF_AT + 4],
                                          jnp.int32), -2)
    wide_c = None
    if wide is not None:
        dtype, Fw = wide
        at = _LEAF_AT + 4
        wide_c = planes_value(p[at:at + jnp.dtype(dtype).itemsize * Fw],
                              dtype, (Fw,))
    return bins_s[:F, :T], g, h, c, leaf_c, wide_c
