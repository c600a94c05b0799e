"""Faults planted under the timed path, to show that the check fails them.

Each one wraps the engine's paged decode call, the step the window runs
most, and is planted by ``planted(cell.build_engine, name)``:

    state_unchanged   the step writes nothing: the cache comes back as it
                      went in
    token_altered     the token is altered where it is produced: the
                      logits are shifted by one token id
    wrong_page        every row's block table is shifted by one page, so
                      the step reads and writes the wrong pages of its cache
"""
from __future__ import annotations


def _state_unchanged(decode, cache, tokens, lengths, tables):
    logits, _ = decode(cache, tokens, lengths, tables)
    return logits, cache


def _token_altered(decode, cache, tokens, lengths, tables):
    import jax.numpy as jnp
    logits, new = decode(cache, tokens, lengths, tables)
    return jnp.roll(logits, 1, axis=-1), new


def _wrong_page(decode, cache, tokens, lengths, tables):
    import jax.numpy as jnp
    return decode(cache, tokens, lengths, jnp.roll(tables, 1, axis=1))


FAULTS = {"state_unchanged": _state_unchanged,
          "token_altered": _token_altered,
          "wrong_page": _wrong_page}


def planted(build, name: str):
    """``build`` (``cell.build_engine``) with fault ``name`` planted in
    every engine it builds."""
    fault = FAULTS[name]

    def build_faulty(*args, **kw):
        eng = build(*args, **kw)
        decode = eng.backend.paged_decode

        def paged_decode(cache, tokens, lengths, tables):
            return fault(decode, cache, tokens, lengths, tables)
        eng.backend.paged_decode = paged_decode
        return eng
    return build_faulty
