"""Faults planted in the timed path, each of which the comparison that
decides ``correct`` has to catch. Each takes a built ``ServeEngine`` and
wraps its compiled decode program in place.

``alter_token``: the greedy token of slot 0 moves to another id where
the decode program produces it. ``stale_state``: the decode program
hands back the KV pool it was given, so no decoded token's K and V
reach the cache. (The cells run on one chip and take no batch mean, so
the exchange between chips and the half-batch fault do not apply.)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def alter_token(eng) -> None:
    real = eng._decode_c

    def broken(params, tokens, cache, pos, bt):
        logits, cache = real(params, tokens, cache, pos, bt)
        best = jnp.argmax(logits[0])
        bump = jax.nn.one_hot((best + 7) % logits.shape[-1], logits.shape[-1])
        return logits.at[0].add(1e3 * bump), cache

    eng._decode_c = broken


def stale_state(eng) -> None:
    real = eng._decode_c

    def broken(params, tokens, cache, pos, bt):
        logits, _ = real(params, tokens, cache, pos, bt)
        return logits, cache

    eng._decode_c = broken


FAULTS = {"token_altered": alter_token, "state_unchanged": stale_state}
