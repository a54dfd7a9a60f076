"""Pallas TPU kernel for the one-token recurrence of a state-space layer.

A decode step of a Mamba-2 layer updates, per lane, a state ``[H, P, N]``
float32 that is far larger than anything else the layer touches (the
benchmark's: 2 MiB a lane, 134 MB a layer for 64 lanes) and reads the
step's output off the updated state. :func:`state_step` does both in ONE
pass over the state pool, in place, for the lanes the dispatch serves: a
served lane's state crosses HBM once in and once out, a lane that is not
served is neither fetched nor written. ``models/llama.py`` ``ssm_step`` is
the same mathematics in ``jax.numpy`` (every lane, three passes) and the form
the tests hold this kernel to.

Compiled on a TPU and run by the Pallas interpreter elsewhere, as the
kernels of ``ops/attention.py`` are.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import _pallas_call

# VMEM the state blocks of a call may take: one block in and one out, two of
# each in the grid's pipeline. A whole lane of the benchmark's model (2 MiB)
# fits; a larger state is cut by heads (measured on a v5e, PERF.md section 5:
# the whole lane a grid step is the fastest form, half a lane reads 3-4 %
# slower and a quarter 10-14 %: a cut lane pays a grid step and the small
# operands' padded rows once more for every cut)
_STATE_BLOCKS_BYTES = 8 << 20


def head_block(H: int, P: int, N: int) -> int:
    """Heads of a lane's state in one block: the most that divide ``H`` and
    keep four blocks inside :data:`_STATE_BLOCKS_BYTES`."""
    fit = max(1, _STATE_BLOCKS_BYTES // (4 * P * N * 4))
    return max(hb for hb in range(1, H + 1) if H % hb == 0 and hb <= fit)


def served_lanes(active: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """``active`` [B] bool -> (lanes [B] int32, count [] int32): the served
    lanes in rising order in the first ``count`` slots and the LAST served
    lane again in every slot behind them (lane 0 where none is served),
    which is how :func:`state_step` wants its grid told what to skip."""
    B = active.shape[0]
    count = jnp.sum(active, dtype=jnp.int32)
    slot_of = jnp.cumsum(active, dtype=jnp.int32) - 1               # [lane]
    slot = jnp.minimum(jnp.arange(B, dtype=jnp.int32), count - 1)
    hit = active[None, :] & (slot_of[None, :] == slot[:, None])     # [slot, lane]
    lanes = jnp.sum(jnp.where(hit, jnp.arange(B, dtype=jnp.int32)[None], 0),
                    axis=1, dtype=jnp.int32)
    return lanes, count


def _state_kernel(layer_ref, lanes_ref, count_ref, s_ref, a_ref, x_ref, bc_ref,
                  y_ref, o_ref, *, heads: int):
    """One (lane slot, head block) of the grid. ``s_ref`` / ``o_ref`` [Hb, P,
    N]: the lane's state block in and out (one buffer of the pool); ``a_ref``
    [1, H] in SMEM: the lane's decays, scalars; ``x_ref`` [P, Hb]: ``dt x X``
    TRANSPOSED, a head's values down a COLUMN over P, which is the state's
    sublane axis (a row over P would have to be turned in the kernel);
    ``bc_ref`` [2, N]: B and C; ``y_ref`` [P, Hb]: the read-out, transposed
    likewise. A slot past the served count maps to the block before it
    (``state_step``'s index maps) and does nothing: Pallas neither fetches
    nor writes a block whose index did not move."""
    del layer_ref, lanes_ref
    slot, j, count = pl.program_id(0), pl.program_id(1), count_ref[0]

    @pl.when(slot < count)
    def _():
        b_row = bc_ref[0:1, :]                                     # [1, N]
        # the read-out as a matrix product with C in every one of Hb rows:
        # each column of the result is y, so column h is stored where it
        # belongs with no move across lanes. (Measured on a v5e, PERF.md
        # section 5: ``sum(new * C, -1)``, a reduction over the minor axis a
        # row, holds the kernel 8 % above what its copies take; the product
        # at HIGHEST does not)
        c_rows = jnp.broadcast_to(bc_ref[1:2, :], (heads, bc_ref.shape[1]))
        for h in range(heads):
            new = (a_ref[0, j * heads + h] * s_ref[h]
                   + x_ref[:, h:h + 1] * b_row)                    # [P, N]
            o_ref[h] = new
            y = jax.lax.dot_general(
                new, c_rows, (((1,), (1,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)                # [P, Hb]
            y_ref[:, h:h + 1] = y[:, h:h + 1]

    # nothing served: every step maps to ONE block, which is written back
    # once, so it has to hold what was there
    @pl.when((count == 0) & (slot == 0) & (j == 0))
    def _():
        o_ref[...] = s_ref[...]
        y_ref[...] = jnp.zeros_like(y_ref)


def state_step(pool: jax.Array, layer, lanes: jax.Array, count: jax.Array,
               a: jax.Array, dtx: jax.Array, Bm: jax.Array, Cm: jax.Array,
               *, interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """One decode step of ONE state-space layer over the whole state pool.

    pool: [L, B, H, P, N] float32, the WHOLE pool, updated in place where the
      caller donates it (``layer``, an int or a traced int32 scalar, picks
      the layer inside the kernel's block copies: no caller slices the pool)
    lanes, count: :func:`served_lanes` of the dispatch's ``active``
    a: [B, H] the decay ``exp(dt A)``; dtx: [B, H, P] ``dt x X``; Bm, Cm:
      [B, N] (all float32; row b IS lane b)

    For each served lane b: ``pool[layer, b, h, p, n] <- a[b, h] pool[...] +
    dtx[b, h, p] Bm[b, n]``, and ``y[b, h, p] = sum_n pool[layer, b, h, p, n]
    Cm[b, n]`` of the UPDATED state. A lane that is not served keeps its
    state because nothing touches it, and its row of ``y`` is whatever was
    there: the caller masks it. -> (y [B, H, P] float32, pool)."""
    L, B, H, P, N = pool.shape
    Hb = head_block(H, P, N)
    nj = H // Hb
    f32 = jnp.float32
    # dt x X by (lane, head block), transposed: [B, nj, P, Hb]
    xt = dtx.astype(f32).reshape(B, nj, Hb, P).transpose(0, 1, 3, 2)
    bc = jnp.stack([Bm.astype(f32), Cm.astype(f32)], axis=1)      # [B,2,N]
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    count = jnp.asarray(count, jnp.int32).reshape(1)

    def block(slot, j, layer, lanes, count):
        # a slot nobody serves stays on the last block of the lane before it
        return lanes[slot], jnp.where(slot < count[0], j, nj - 1)

    def state_map(slot, j, layer, lanes, count):
        return (layer[0], *block(slot, j, layer, lanes, count), 0, 0)

    def small_map(slot, j, layer, lanes, count):
        return (*block(slot, j, layer, lanes, count), 0, 0)

    def lane_map(slot, j, layer, lanes, count):
        return lanes[slot], 0, 0

    state_spec = pl.BlockSpec((None, None, Hb, P, N), state_map)
    small_spec = pl.BlockSpec((None, None, P, Hb), small_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, nj),
        in_specs=[
            state_spec,
            pl.BlockSpec((None, 1, H), lane_map,
                         memory_space=pltpu.MemorySpace.SMEM),
            small_spec,
            pl.BlockSpec((None, 2, N), lane_map),
        ],
        out_specs=[small_spec, state_spec],
    )
    y, pool = _pallas_call(
        functools.partial(_state_kernel, heads=Hb),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, nj, P, Hb), f32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # operands count from the three prefetched scalars: the pool is 3
        input_output_aliases={3: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(layer, lanes, count, pool, a.astype(f32).reshape(B, 1, H), xt, bc)
    return y.transpose(0, 1, 3, 2).reshape(B, H, P), pool
