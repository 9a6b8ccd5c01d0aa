"""In-place cohort scatter for the mixed-exit cache re-join (Pallas).

The cohort-major decode path (``core/exec.py`` ``_mixed``) runs each cohort's
segment step over a zero-copy view of the cache slab, then re-joins the C
per-cohort outputs into the full slab.  The seeded re-join is
``jnp.concatenate(parts, axis=1)`` — and PR 4's layout study documented that
XLA does NOT elide the equivalent ``.at[:, lo:hi].set`` scatter inside the
surrounding ``while_loop`` + ``cond``: every mixed step paid a full-slab
materialization even though each cohort only produced ``B/C`` fresh rows.

:func:`cohort_scatter` replaces that re-join with an aliased partial-write
``pallas_call``: the destination slab is input 0 AND the output buffer
(``input_output_aliases={0: 0}``), the grid covers only the target cohort's
blocks, and the kernel copies the cohort's rows into place.  Blocks the grid
never visits keep the aliased input's bytes — the other cohorts' rows are
untouched, no full-slab copy is issued by the kernel itself.  Chaining the
call once per cohort (``dst = cohort_scatter(dst, part, c, C)``) rebuilds the
slab with C cohort-sized writes instead of one B-sized concat.

``c`` and ``C`` are Python ints (the cohort loop in ``_mixed`` is unrolled),
so the block index maps are static — no dynamic-slice lowering.  The grid
walks (layer, cohort row, row tile) over a ``(L, B, R/128, 128)`` view, so
a cohort of any row count (lane batch 8 in two cohorts is 4 rows) meets
the TPU's (8, 128) block tiling rule.

Semantics are bit-identical to the concat (pinned by tests); only the memory
traffic changes.  Non-array-friendly leaves (cohort axis missing, or a
trailing extent the TPU layout can't partial-write) fall back to
``dst.at[...].set(src)`` — same bytes, XLA's choice of copy.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.backend import resolve_interpret


LANES = 128
# rows of LANES elements per block: 2048 x 128 bf16 = 512 KiB, so the
# double-buffered source and destination blocks stay far inside VMEM
MAX_BLOCK_ROWS = 2048


def _scatter_kernel(dst_ref, src_ref, out_ref):
    del dst_ref  # aliased to out_ref; never read
    out_ref[...] = src_ref[...]


@partial(jax.jit, static_argnames=("c", "C", "interpret"))
def _scatter(dst, src, c: int, C: int, interpret: bool):
    L, B = dst.shape[0], dst.shape[1]
    Bc = B // C
    R = math.prod(dst.shape[2:])
    # each batch row's trailing extent viewed as (rows, lanes): the block's
    # last two dims are then (8k, 128) or full, whatever Bc is
    lanes = LANES if R % LANES == 0 else R
    rows = R // lanes
    tr = math.gcd(rows, MAX_BLOCK_ROWS)
    if tr % 8:
        tr = rows
    d4 = dst.reshape(L, B, rows, lanes)
    s4 = src.reshape(L, Bc, rows, lanes)
    blk = (1, 1, tr, lanes)
    dst_spec = pl.BlockSpec(blk, lambda l, b, r: (l, c * Bc + b, r, 0))
    out = pl.pallas_call(
        _scatter_kernel,
        grid=(L, Bc, rows // tr),
        in_specs=[dst_spec, pl.BlockSpec(blk, lambda l, b, r: (l, b, r, 0))],
        out_specs=dst_spec,
        out_shape=jax.ShapeDtypeStruct(d4.shape, d4.dtype),
        input_output_aliases={0: 0},
        interpret=interpret,
    )(d4, s4)
    return out.reshape(dst.shape)


def cohort_scatter(dst, src, c: int, C: int, *, interpret=None):
    """Write cohort ``c``'s rows ``src`` into ``dst`` along axis 1.

    ``dst``: (L, B, ...); ``src``: (L, B // C, ...) — the cohort's segment
    output.  Returns the updated slab; the destination buffer is aliased so
    the compiled program updates in place (untouched cohorts keep their
    bytes).  Bit-identical to ``dst.at[:, c*Bc:(c+1)*Bc].set(src)``.
    """
    interpret = resolve_interpret(interpret)
    if dst.ndim < 2 or dst.shape[1] % C != 0:
        lo = c * (dst.shape[1] // C) if dst.ndim >= 2 else 0
        return dst.at[:, lo:lo + src.shape[1]].set(src)
    Bc = dst.shape[1] // C
    R = math.prod(dst.shape[2:])
    # compiled TPU lowering needs a lane-aligned trailing extent for a
    # partial write; oddball leaves take the plain XLA scatter instead
    if not interpret and (R % LANES != 0 or dst.dtype == jnp.bool_):
        return dst.at[:, c * Bc:(c + 1) * Bc].set(src)
    if dst.dtype == jnp.bool_:
        out = _scatter(dst.astype(jnp.int8), src.astype(jnp.int8), c, C,
                       interpret)
        return out.astype(jnp.bool_)
    return _scatter(dst, src, c, C, interpret)


def cohort_scatter_tree(dst_tree, src_tree, c: int, C: int, *, interpret=None):
    """Tree-mapped :func:`cohort_scatter` over matching cache pytrees."""
    return jax.tree_util.tree_map(
        lambda d, s: cohort_scatter(d, s, c, C, interpret=interpret),
        dst_tree, src_tree)
