"""Named RNG streams (DESIGN.md §14).

Every independent randomness consumer in the simulator draws from
``rng_stream(seed, name)`` instead of an ad-hoc seed offset.  The seed
code used bare offsets (``seed+1`` noise, ``seed+2`` policy, ``seed+3``
churn) and salted ``(salt, seed)`` tuples for the later planes — which
meant a new plane picking ``seed+2`` would silently alias the policy
draws (the campaign's ``seed_blocks`` already share that offset BY
DESIGN: each RandomChoice block must replay its serial run's policy
stream).  The helper pins the legacy names onto their historical
identities bit-for-bit (the goldens in ``tests/test_golden_sim.py``
depend on it) and derives every NEW stream from a crc32 hash of its
name, so streams cannot collide by arithmetic accident.
"""
from __future__ import annotations

import zlib
from typing import Union

import numpy as np

#: legacy integer-offset streams — pinned: changing these moves goldens
_LEGACY_OFFSETS = {"topology": 0, "noise": 1, "policy": 2, "churn": 3}
#: legacy salted-tuple streams — pinned for the same reason.  ``arrival``
#: is keyed by ``stream_seed`` (shared across seeds for the campaign's
#: lockstep batching); the rest by ``cfg.seed``.
_LEGACY_SALTS = {"arrival": 17, "noise_streamed": 29, "drift": 31,
                 "preempt": 37}
#: tuple salts already taken — a hashed stream landing on one would
#: alias a legacy stream whenever the base seeds coincide
_RESERVED = frozenset(_LEGACY_SALTS.values())


def rng_seed(seed: int, name: str) -> Union[int, tuple]:
    """The ``default_rng`` key stream ``name`` draws under base ``seed``.

    Legacy names resolve to their historical offsets/salts; unknown
    names hash to a ``(crc32(name), seed)`` tuple (tuple keys feed
    ``SeedSequence`` entropy, so they can never collide with the bare
    integer offsets, and the hash keeps them clear of each other).
    """
    if name in _LEGACY_OFFSETS:
        return seed + _LEGACY_OFFSETS[name]
    if name in _LEGACY_SALTS:
        return (_LEGACY_SALTS[name], seed)
    salt = zlib.crc32(name.encode()) % (2 ** 31)
    if salt in _RESERVED:  # pragma: no cover - crc32 of a future name
        salt += 41
    return (salt, seed)


def rng_stream(seed: int, name: str) -> np.random.Generator:
    """A fresh ``Generator`` on the named stream."""
    return np.random.default_rng(rng_seed(seed, name))


def rng_from_key(key) -> np.random.Generator:
    """A ``Generator`` from an externally pinned key — the sanctioned
    escape hatch for callers that must replay a stream whose identity
    is fixed elsewhere (the campaign's RandomChoice ``seed_blocks``:
    block *i* must draw exactly what a serial run under ``seed_i``
    would, DESIGN.md §10).  Centralised here so the rng-stream linter
    (``repro.analysis.rng_audit``) can forbid raw ``default_rng``
    construction everywhere else in ``core/``."""
    return np.random.default_rng(key)


def rng_resume(state: dict) -> np.random.Generator:
    """A ``Generator`` at a saved ``bit_generator.state``: it draws
    exactly what the saved generator drew next (the deferred prediction
    noise, ``simulator._NoiseBlock``)."""
    bit_gen = getattr(np.random, state["bit_generator"])()
    bit_gen.state = state
    return np.random.Generator(bit_gen)


def rng_key(seed: int, name: str):
    """A jax PRNG key on the named stream (lazy jax import — numpy-only
    consumers of this module never pay for it).

    Hashed names fold their salt into the key so two named key streams
    relate exactly like two named ``Generator`` streams: distinct names
    -> statistically independent keys under every base seed.
    """
    import jax

    ident = rng_seed(seed, name)
    if isinstance(ident, tuple):
        salt, base = ident
        return jax.random.fold_in(jax.random.PRNGKey(base), salt)
    return jax.random.PRNGKey(ident)
