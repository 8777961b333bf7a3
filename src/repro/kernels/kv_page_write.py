"""In-place write of one decode token's K/V into a stacked page pool.

The serving engine's decode step carries every layer's page pool as one
stack, ``(L, NP, Hkv, ps, hd)``, through its layer scan.  Each layer must
write the new token's K and V into its slot's current page before the
paged attention kernel (``kernels/tcec_paged_attention.py``) reads it.  An
XLA scatter at ``[layer, page, :, off]`` writes an ``(Hkv, hd)`` slab that
is strided in the head-major layout, so XLA re-lays-out the whole stack
for it; this kernel instead aliases the stacked pools in and out
(``input_output_aliases``) and touches one page per slot.

One grid step per slot (``arbitrary``: steps run in order): the page
``pool[layer, page[b]]`` of all heads, ``(Hkv, ps, hd)``, is fetched into
VMEM, row ``off[b]`` is replaced by the token, and the page is written
back; every other page of the stack is never read or written.  Inactive
slots point at the scrap page 0 and may overwrite one another there,
which is harmless; an active slot's current page belongs to it alone.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .tcec_matmul import compiler_params


def _kv_write_kernel(page_ref, off_ref, layer_ref, kn_ref, vn_ref, kp_ref,
                     vp_ref, ko_ref, vo_ref):
    off = off_ref[pl.program_id(0)]
    for new_ref, pool_ref, out_ref in ((kn_ref, kp_ref, ko_ref),
                                       (vn_ref, vp_ref, vo_ref)):
        page = pool_ref[0, 0].astype(jnp.float32)            # (Hkv, ps, d)
        new = jnp.broadcast_to(new_ref[0].astype(jnp.float32), page.shape)
        row = jax.lax.broadcasted_iota(jnp.int32, page.shape, 1)
        out_ref[0, 0] = jnp.where(row == off, new, page).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def kv_page_write_pallas(k_pool, v_pool, k_new, v_new, page, off, layer, *,
                         interpret: bool = False):
    """k_pool/v_pool: (L, NP, Hkv, ps, hd[v]); k_new/v_new: (B, Hkv, 1,
    hd[v]) in the pools' dtypes; page, off: (B,) i32; layer: (1,) i32.
    Returns the pools with ``pool[layer, page[b], :, off[b]] = new[b]``,
    updated in place (the pools are aliased to the outputs)."""
    _, _, Hkv, ps, hd = k_pool.shape
    hdv = v_pool.shape[4]
    B = k_new.shape[0]
    assert k_new.shape == (B, Hkv, 1, hd), (k_new.shape, k_pool.shape)
    assert v_new.shape == (B, Hkv, 1, hdv), (v_new.shape, v_pool.shape)

    def pool_spec(width):
        return pl.BlockSpec((1, 1, Hkv, ps, width),
                            lambda b, pg, of, lyr: (lyr[0], pg[b], 0, 0, 0))

    def new_spec(width):
        return pl.BlockSpec((1, Hkv, 1, width),
                            lambda b, *_: (b, 0, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[new_spec(hd), new_spec(hdv), pool_spec(hd),
                  pool_spec(hdv)],
        out_specs=[pool_spec(hd), pool_spec(hdv)],
    )
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = compiler_params(("arbitrary",))
    return pl.pallas_call(
        _kv_write_kernel,
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
                   jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype)),
        # operands count the three scalar-prefetch arrays first
        input_output_aliases={5: 0, 6: 1},
        interpret=interpret,
        **kwargs,
    )(page, off, layer, k_new, v_new, k_pool, v_pool)


def kv_page_write(k_pool, v_pool, k_new, v_new, page, off, layer, *,
                  interpret: bool | None = None):
    """Public entry: write one token per slot into a stacked pool.

    k_pool/v_pool: (L, NP, Hkv, ps, hd[v]) head-major page pools; k_new/
    v_new: (B, Hkv, hd[v]) the slots' new K/V (cast to the pools' dtype);
    page, off: (B,) i32, each slot's current page and the row in it;
    layer: the stack entry (an int or traced scalar).  Returns ``(k_pool,
    v_pool)`` equal to ``pool.at[layer, page, :, off].set(new)``."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, Hkv = k_new.shape[:2]
    return kv_page_write_pallas(
        k_pool, v_pool,
        k_new.astype(k_pool.dtype).reshape(B, Hkv, 1, k_new.shape[2]),
        v_new.astype(v_pool.dtype).reshape(B, Hkv, 1, v_new.shape[2]),
        jnp.asarray(page, jnp.int32), jnp.asarray(off, jnp.int32),
        jnp.asarray(layer, jnp.int32).reshape(1), interpret=interpret)
