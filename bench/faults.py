"""Faults planted in the timed path, for the checks that ``correct`` has to
fail: each replaces the engine instance's decode block with a broken one.
They are planted before the warm-up, so that the window compiles nothing.

    python3 bench/calibrate.py --workload granite-moe-1b.decode \
        --seeds 5 --seconds 51 --fault cache_unchanged
"""
from __future__ import annotations

import jax


def altered_token(eng) -> None:
    """The fourth token of every block comes out one higher."""
    tick = eng._tick_block
    vocab = eng.model.cfg.vocab_size

    def broken(*args):
        *rest, out_buf, out_cnt = tick(*args)
        return (*rest, (out_buf.at[:, 3].add(1)) % vocab, out_cnt)

    eng._tick_block = broken


def cache_unchanged(eng) -> None:
    """The decode block hands back the key/value cache it was given.  Built
    as one program from the engine's own block, so that no second copy of
    the page pool has to fit beside it."""
    raw = eng._tick_block.__wrapped__

    def broken(params, cache, *args):
        _, *rest = raw(params, cache, *args)
        return (cache, *rest)

    eng._tick_block = jax.jit(broken, donate_argnums=(1, 3, 4, 5, 6, 7))


def half_slots_silent(eng) -> None:
    """Every other slot delivers no tokens: half the batch left out of what
    the block hands back.  (The engine fills the lowest free slot first, so
    the even slots are always among those in use.)"""
    tick = eng._tick_block

    def broken(*args):
        *rest, out_cnt = tick(*args)
        return (*rest, out_cnt.at[::2].set(0))

    eng._tick_block = broken


FAULTS = {f.__name__: f for f in (altered_token, cache_unchanged,
                                  half_slots_silent)}
