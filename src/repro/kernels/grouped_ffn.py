"""Grouped SwiGLU over the experts a chip holds (Pallas TPU).

The rows of ``x`` are sorted by expert into groups: group ``e`` starts
at block ``start[e]`` (blocks of ``tile`` rows) and holds ``rows[e]``
live rows, the rest of its last block being zero padding.  For each
held expert the kernel computes ``silu(x @ w_gate) * (x @ w_up) @
w_down`` on that expert's live rows only, rounded up to the sublane
tile, and leaves every other row of the output unwritten.

Grid ``(expert, row chunk, f tile)``, the f tile innermost: one step
reads one ``(d, tf)`` tile of the expert's gate and up weights and one
``(tf, d)`` tile of its down weights, and adds its part of the down
projection into an f32 accumulator of the chunk's rows, written out
after the last f tile.  The expert and chunk are the outer axes, so an
expert's weights are streamed once for each chunk of its rows; a decode
batch (at most ``tile`` rows per expert) has one chunk, so each weight
is read once a call.  Chunks past an expert's rows compute nothing and
point every operand at the block of the step before (weights) or at a
spare block past the last group (rows), so that they copy nothing.
Group starts and row counts come in as scalar prefetch, and so does the
layer: the weights are the whole stack of layers, ``(L, E, ...)``, read
in place, since a layer's slice taken outside the kernel (as a scan over
layers takes it) is copied before a kernel can read it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BIG_ROWS = 128         # rows of one MXU pass where an expert has enough


def sublane_rows(dtype) -> int:
    """Rows of one sublane tile of ``dtype`` (16 for 2-byte types)."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def f_tile(f: int) -> int:
    """Columns of the expert width one grid step reads."""
    for tf in (256, 128):
        if f % tf == 0:
            return tf
    return f


def _kernel(start_ref, rows_ref, layer_ref, x_ref, wg_ref, wu_ref, wd_ref,
            o_ref, acc_ref, *, tile, sub):
    e, c, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    live = jnp.clip(rows_ref[e] - c * tile, 0, tile)
    live = pl.cdiv(live, sub) * sub

    @pl.when(j == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def rows_through(r0, n):
        r0 = pl.multiple_of(r0, sub)
        xs = x_ref[pl.ds(r0, n), :]
        g = jnp.dot(xs, wg_ref[0, 0], preferred_element_type=jnp.float32)
        u = jnp.dot(xs, wu_ref[0, 0], preferred_element_type=jnp.float32)
        h = (jax.nn.silu(g) * u).astype(wd_ref.dtype)
        acc_ref[pl.ds(r0, n), :] += jnp.dot(
            h, wd_ref[0, 0], preferred_element_type=jnp.float32)

    big = BIG_ROWS if tile % BIG_ROWS == 0 else tile
    n_big = live // big

    def big_step(i, carry):
        rows_through(i * big, big)
        return carry

    def small_step(i, carry):
        rows_through(n_big * big + i * sub, sub)
        return carry

    jax.lax.fori_loop(0, n_big, big_step, 0)
    jax.lax.fori_loop(0, (live - n_big * big) // sub, small_step, 0)

    @pl.when(j == pl.num_programs(2) - 1)
    def _write():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def grouped_ffn(x, w_gate, w_up, w_down, start, rows, layer, *, tile: int,
                chunks: int, interpret: bool = False):
    """x: (N, d) rows in groups of whole ``tile``-row blocks, the last
    block spare; w_gate, w_up: (L, E, d, f); w_down: (L, E, f, d); start:
    (E,) first block of each group; rows: (E,) live rows of each group,
    at most ``chunks * tile``; layer: the index into the weights' first
    axis.  Returns (N, d) in ``x``'s dtype, defined on each group's live
    rows."""
    n, d = x.shape
    _, n_exp, _, f = w_gate.shape
    assert n % tile == 0, (n, tile)
    tf = f_tile(f)
    nj = f // tf
    spare = n // tile - 1
    sub = sublane_rows(x.dtype)

    def live(e, c, rows_ref):
        return c * tile < rows_ref[e]

    def row_block(e, c, j, start_ref, rows_ref, layer_ref):
        return (jnp.where(live(e, c, rows_ref), start_ref[e] + c, spare), 0)

    def w_col(e, c, j, rows_ref):
        # a chunk that computes nothing keeps the previous step's tile
        keep = jnp.logical_or(c == 0, live(e, c, rows_ref))
        return jnp.where(keep, j, nj - 1)

    def in_w(e, c, j, s, r, layer_ref):           # (d, tf) tiles
        return (layer_ref[0], e, 0, w_col(e, c, j, r))

    def out_w(e, c, j, s, r, layer_ref):          # (tf, d) tiles
        return (layer_ref[0], e, w_col(e, c, j, r), 0)

    in_specs = [pl.BlockSpec((tile, d), row_block),
                pl.BlockSpec((1, 1, d, tf), in_w),
                pl.BlockSpec((1, 1, d, tf), in_w),
                pl.BlockSpec((1, 1, tf, d), out_w)]
    wbytes = jnp.dtype(w_gate.dtype).itemsize
    xbytes = jnp.dtype(x.dtype).itemsize
    vmem = (2 * 3 * d * tf * wbytes          # double-buffered weight tiles
            + 2 * 2 * tile * d * xbytes      # rows in and out
            + tile * d * 4)                  # accumulator
    return pl.pallas_call(
        functools.partial(_kernel, tile=tile, sub=sub),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_exp, chunks, nj),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((tile, d), row_block),
            scratch_shapes=[pltpu.VMEM((tile, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((n, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=int(vmem * 1.25) + (8 << 20)),
        interpret=interpret,
    )(start.astype(jnp.int32), rows.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), x, w_gate, w_up, w_down)
