"""Faults planted in the timed path, to show the comparison catches them.

Each is a context manager that patches the serving engine for as long as
it is open.  ``tests/test_correct.py`` runs a whole cell at smoke widths
under each and sees ``correct`` come out false; ``calibrate.py`` reads
the compared numbers under them on the chip at the cell's own size.
"""
from __future__ import annotations

import contextlib
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


FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch,
          "token_altered": token_altered}
