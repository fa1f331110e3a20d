"""Decode-window sampling-key contract.

The engine's decode window forks one subkey off the engine key per
window (``self._key, sub = jax.random.split(self._key)``) and then
chains INSIDE the window: every step splits the window key once and
samples with the subkey.  The contract: the window's draws equal the
per-token stream's — what a caller who drives the same window key
through one ``split_step`` a token on the host would draw — which
reduces to reproducing this exact key sequence; ``jax.random.split``
is deterministic, so "same splits in the same order" IS the whole
contract.

This module is the single home of that derivation: the step program,
the ``lax.scan``/``while_loop`` window bodies, capsule replay, the
speculative draft and the tests all derive step keys through
``split_step``, so a drive-by "optimization" (folding in a step index,
splitting n keys up front, reordering the split against the sample)
cannot silently fork them.  Note what the contract is NOT: keys are
not indexed by ABSOLUTE step number — step j of a window uses the j-th
split of the WINDOW key, so early exit inside a window (all rows done)
skips splits without perturbing the engine key.

Per-ROW draws fold the batch row index into the step subkey
(``fold_row``), so a request's token stream depends on the key chain
and its row id but NOT on which other requests share the batch.  The
live engine always folds the physical row (``draw_base=0`` + row i
folds i); capsule replay re-pins a request decoded in row r by placing
it in row 0 and passing ``draw_base=r``, so row 0 folds the original
r.  Greedy decoding ignores keys entirely, which is why it is
bit-identical across batch shapes without any of this.

``sample_logits`` is re-exported so window bodies import their whole
sampling surface from one place.
"""
from __future__ import annotations

from ..nn.generation import sample_logits

__all__ = ["split_step", "window_keys", "key_fingerprint",
           "key_from_fingerprint", "sample_logits", "fold_row",
           "spec_window_keys", "spec_draw_key"]

# Speculative windows fork ONE subkey off the engine key like every
# other window and derive every draw inside it from that fork via
# fold_in tags — the engine key stream is identical whether a window
# decodes plainly or speculatively, so capsules replay across both.
_SPEC_DRAFT_TAG = 0x5bec0d01     # draft propose chain root
_SPEC_ACCEPT_TAG = 0x5bec0d02    # acceptance-uniform root
_SPEC_RESAMPLE_TAG = 0x5bec0d03  # rejection-resample / bonus root


def spec_window_keys(key):
    """Derive one speculative window's (draft, accept, resample) key
    roots from its forked window key.  THE single definition — the
    live window and capsule replay both derive here, so the two
    cannot drift.  The draft root seeds the propose program's
    ``split_step`` chain; accept/resample roots seed per-(step, row)
    draws via ``spec_draw_key``."""
    import jax

    return (jax.random.fold_in(key, _SPEC_DRAFT_TAG),
            jax.random.fold_in(key, _SPEC_ACCEPT_TAG),
            jax.random.fold_in(key, _SPEC_RESAMPLE_TAG))


def spec_draw_key(root, step: int, row: int):
    """Per-(step, row) acceptance/resample draw key: the step folds
    first, then the row via ``fold_row`` — mirroring the decode
    window's ``split_step`` × ``fold_row`` grid, so a request's
    acceptance draws depend on its draw id (``draw_base + batch
    row``) and never on batch packing.  Replay re-pins a request by
    passing its CAPTURED row, exactly like token sampling."""
    import jax

    return fold_row(jax.random.fold_in(root, int(step)), int(row))


def fold_row(key, row):
    """Per-row sample key: ``jax.random.fold_in(step_subkey, row)``.

    THE single definition of the row fold — ``sample_logits`` (via
    ``row_ids=``), the window bodies, and the replay oracle all derive
    per-row keys here so they cannot drift.  ``row`` is the request's
    draw id: physical batch row on the live path, the CAPTURED row on
    replay (threaded in as ``draw_base + row_index``).
    """
    import jax

    return jax.random.fold_in(key, row)


def split_step(key):
    """One decode step's key derivation: ``(next_key, step_subkey)``.

    Exactly ``jax.random.split(key)`` unpacked — kept as THE single
    definition so host-chained dispatch and the scanned window bodies
    cannot drift.  Traceable (used inside jit/scan/while bodies) and
    callable eagerly (tests, host admission path).
    """
    import jax

    next_key, sub = jax.random.split(key)
    return next_key, sub


def key_fingerprint(key):
    """Portable record of a PRNG key: its raw uint32 words as a plain
    int list (JSON-able — request capsules carry window keys across
    replicas in migration packages and spill files).  Inverse of
    ``key_from_fingerprint``: round-tripping a key and splitting it
    reproduces the original split chain exactly, because the words ARE
    the key's whole state."""
    import jax
    import numpy as np

    try:
        words = jax.random.key_data(key)
    except (AttributeError, TypeError):
        words = key  # legacy raw uint32-vector key
    return [int(w) for w in np.asarray(words).ravel()]


def key_from_fingerprint(words):
    """Rebuild a decode-window key from ``key_fingerprint`` output.
    Returns the legacy uint32-vector form, which every sampling entry
    point in this repo accepts (``jax.random`` treats it as a
    threefry2x32 key)."""
    import jax.numpy as jnp

    return jnp.asarray(list(words), dtype=jnp.uint32)


def window_keys(key, n_steps: int):
    """Host-side mirror of an ``n_steps`` window's key sequence:
    ``([sub_0, ..., sub_{n_steps-1}], final_key)``.

    Reference oracle for tests that pin the scanned window's sampling
    draws against manual chaining; the engine itself never calls this
    (its windows derive keys step by step via ``split_step``).
    """
    subs = []
    for _ in range(int(n_steps)):
        key, sub = split_step(key)
        subs.append(sub)
    return subs, key
