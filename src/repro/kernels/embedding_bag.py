"""Pallas TPU embedding-bag kernel (the paper's dominant operator for
DLRM-RMC1/2 and DIN — Fig. 3 "embedding dominated").

TPU adaptation of the CPU gather+pool loop.  The table stays in HBM
(``memory_space=pl.ANY``) and is never blocked: a (1, D) row block would
break the TPU's (8, 128) tiling rule, so each indexed row is copied by its
own DMA into a VMEM scratch instead.  The grid tiles the bags, TILE_B at a
time; the tile's (TILE_B, H) ids arrive in SMEM.  Hotness step k fetches
one row per bag of the tile into one of two VMEM slots while the rows of
step k-1 are summed, so the gather overlaps the pooling.  Bytes moved =
H rows fetched + 1 output row written per bag — the streaming minimum.

D is padded to the 128-lane boundary and B to a multiple of TILE_B by the
wrapper in ``ops.py``, which also clamps ids into the table.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def embedding_bag(table: jax.Array, idx: jax.Array, *, mode: str = "sum",
                  tile_b: int = 8, interpret: bool = False) -> jax.Array:
    """table (V, D), idx (B, H) int32 → (B, D) pooled (sum/mean).

    B must be a multiple of ``tile_b``, D a multiple of 128 and every id in
    [0, V) (``ops`` pads and clamps); V is unconstrained (rows stream from
    HBM).
    """
    b, h = idx.shape
    _, d = table.shape
    if b % tile_b:
        raise ValueError(f"batch {b} is not a multiple of tile_b {tile_b}")

    def kernel(idx_ref, table_ref, out_ref, buf, sem):
        def row_copy(k, i, slot):
            return pltpu.make_async_copy(
                table_ref.at[pl.ds(idx_ref[i, k], 1)],
                buf.at[slot, pl.ds(i, 1)], sem.at[slot])

        def fetch(k, slot):
            for i in range(tile_b):
                row_copy(k, i, slot).start()

        fetch(0, 0)

        def step(k, carry):
            acc, comp = carry
            slot = k % 2

            @pl.when(k + 1 < h)
            def _prefetch():
                fetch(k + 1, 1 - slot)

            for i in range(tile_b):
                row_copy(k, i, slot).wait()
            # Kahan-compensated f32 accumulation (comp carries the rounding
            # error of each partial sum).  Plain running `+=` drifts by an
            # ulp per step, which shows against the oracle when the H rows
            # nearly cancel — and bf16 tables would lose ~2^-8 per step
            # uncompensated.
            y = buf[slot].astype(jnp.float32) - comp
            t = acc + y
            return t, (t - acc) - y

        zeros = jnp.zeros((tile_b, d), jnp.float32)
        acc, _ = jax.lax.fori_loop(0, h, step, (zeros, zeros))
        out_ref[...] = acc

    out = pl.pallas_call(
        kernel,
        grid=(b // tile_b,),
        in_specs=[pl.BlockSpec((tile_b, h), lambda bt: (bt, 0),
                               memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((tile_b, d), lambda bt: (bt, 0)),
        scratch_shapes=[pltpu.VMEM((2, tile_b, d), table.dtype),
                        pltpu.SemaphoreType.DMA((2,))],
        out_shape=jax.ShapeDtypeStruct((b, d), jnp.float32),
        interpret=interpret,
    )(idx, table)
    if mode == "mean":
        out = out / h
    return out.astype(table.dtype)
