"""Faults that a served cell can have, planted under the timed path to show
that the comparison which decides ``correct`` catches each of them.

Each takes the built scheduler and breaks its engine in place.  The
benchmark's own runs plant none; ``bench/run.py --fault <name>`` and the
tests do.  One chip: there is no exchange between chips to leave out.
"""
from __future__ import annotations


def token_altered(sched) -> None:
    """Every sampled token is replaced by the next id in the vocabulary."""
    eng = sched.engine
    sample = eng.sample_checked

    def altered(logits, key):
        tok, ok = sample(logits, key)
        return (tok + 1) % eng.cfg.vocab_size, ok

    eng.sample_checked = altered


def state_unchanged(sched) -> None:
    """The decode step returns the cache it was given: no new key or value
    is ever written, and every step attends the prompt alone."""
    import jax
    eng = sched.engine
    step = jax.jit(lambda w, t, c, p: (eng._decode_impl(w, t, c, p)[0], c))
    eng._decode = lambda t, c, p: step((eng.params, eng.projectors), t, c, p)


def half_batch(sched) -> None:
    """The second half of the batch's rows get the first half's logits."""
    eng = sched.engine
    decode = eng._decode

    def half(t, c, p):
        logits, c = decode(t, c, p)
        h = logits.shape[0] // 2
        return logits.at[h:].set(logits[:h]), c

    eng._decode = half


FAULTS = {f.__name__: f for f in (token_altered, state_unchanged, half_batch)}
