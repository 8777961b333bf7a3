"""Faults planted in the timed path, to show the comparison catches them.

Each is a context manager that patches the serving engine for as long as
it is open.  ``tests/test_correct.py`` (and ``tests/test_mesh.py``, for a
cell over several chips) runs a whole cell at smoke widths under each
and sees ``correct`` come out false; ``calibrate.py`` reads the compared
numbers under them on the chip at the cell's own size.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools

import jax.numpy as jnp


@contextlib.contextmanager
def _patched_decode(wrap):
    from repro.serving import engine as eng_mod
    orig = eng_mod._decode_and_sample

    @functools.wraps(orig)
    def broken(*args, **kw):
        return wrap(orig(*args, **kw), args)
    eng_mod._decode_and_sample = broken
    try:
        yield
    finally:
        eng_mod._decode_and_sample = orig


def state_unchanged():
    """The decode step hands back the page pools it was given."""
    return _patched_decode(lambda out, args: (out[0], out[1], args[1],
                                              out[3]))


def half_batch():
    """The decode step computes the first half of the slots only; the
    rest get token 0."""
    def wrap(out, args):
        toks = out[0]
        keep = jnp.arange(toks.shape[0]) < toks.shape[0] // 2
        return (jnp.where(keep, toks, 0),) + tuple(out[1:])
    return _patched_decode(wrap)


@contextlib.contextmanager
def token_altered():
    """Every request's third served token is replaced by the next id."""
    from repro.serving.engine import Engine
    orig = Engine._accept_token

    def altered(self, req, tok):
        if len(req.out) == 2:
            tok = (tok + 1) % self.cfg.vocab_size
        return orig(self, req, tok)
    Engine._accept_token = altered
    try:
        yield
    finally:
        Engine._accept_token = orig


@contextlib.contextmanager
def row_partials_unsummed():
    """Every fused GEMM under the mesh runs row-parallel (its contraction
    on the ``model`` axis, as ``o_proj`` and ``down_proj`` are laid out)
    and leaves out the ``psum`` that adds the chips' partial products: each
    chip goes on with its own partial."""
    from repro.kernels import shmap
    orig = shmap.matmul_plan

    def unsummed(a_shape, b_shape, mesh):
        n, m = b_shape[-1], mesh.shape.get("model", 1)
        # an N that the axis cannot divide makes the plan shard K
        plan = orig(a_shape, tuple(b_shape[:-1]) + (n * m + 1,), mesh)
        if m == 1 or plan is None or plan.sharded_dim != "K":
            return orig(a_shape, b_shape, mesh)
        bl, ml, _, kl = plan.local
        return dataclasses.replace(plan, psum_axes=(), local=(bl, ml, n, kl))
    shmap.matmul_plan = unsummed
    try:
        yield
    finally:
        shmap.matmul_plan = orig


@contextlib.contextmanager
def shard_pages_zeroed():
    """After the window, the K/V pages that the mesh's first chip holds
    read zero: its share of each pool, along the axis the pool is
    sharded on, in every page."""
    from chipbench import check
    orig = check.resident_record

    def zeroed(engine, req):
        pools = {}
        for name, pool in engine.pools["dense_blocks"].items():
            axis = tuple(pool.sharding.spec).index("model")
            share = pool.shape[axis] // engine.mesh.shape["model"]
            idx = [slice(None)] * pool.ndim
            idx[axis] = slice(0, share)
            pools[name] = pool.at[tuple(idx)].set(0)
        engine.pools = {**engine.pools, "dense_blocks": pools}
        return orig(engine, req)
    check.resident_record = zeroed
    try:
        yield
    finally:
        check.resident_record = orig


FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch,
          "token_altered": token_altered}
#: faults only a cell whose layers are shared over several chips can have
MESH_FAULTS = {"row_partials_unsummed": row_partials_unsummed,
               "shard_pages_zeroed": shard_pages_zeroed}


def for_chips(chips: int) -> dict:
    """The faults a cell on ``chips`` chips can have."""
    return {**FAULTS, **MESH_FAULTS} if chips > 1 else dict(FAULTS)
