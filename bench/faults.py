"""Faults planted in the timed path, to show that the output check
fails when the path is broken underneath: each takes a ``setattr``
(the builtin, or pytest's ``monkeypatch.setattr``) and replaces one
piece of the serving engine. There is no exchange between chips to
break: every cell runs on one chip.

- ``state_unchanged``: a decode step returns its KV caches unchanged,
  so no generated token's keys and values are ever written;
- ``half_batch``: the second half of the batch gets the first half's
  next tokens;
- ``token_altered``: every sampled token is moved to the next id.
"""
import jax.numpy as jnp


def state_unchanged(put):
    from repro.serve.engine import Engine
    orig = Engine._decode_step

    def broken(cfg, params, toks, pos, caches, *rest):
        nxt, done, _, key = orig(cfg, params, toks, pos, caches, *rest)
        return nxt, done, caches, key

    put(Engine, "_decode_step", staticmethod(broken))


def half_batch(put):
    from repro.serve.engine import Engine
    orig = Engine._decode_step

    def broken(cfg, params, toks, pos, caches, *rest):
        B = toks.shape[0]
        nxt, done, caches, key = orig(cfg, params, toks, pos, caches,
                                      *rest)
        return jnp.concatenate([nxt[:B - B // 2], nxt[:B // 2]]), done, \
            caches, key

    put(Engine, "_decode_step", staticmethod(broken))


def token_altered(put):
    from repro.serve import engine
    orig = engine._sample_tokens

    def broken(logits, key, temps):
        return (orig(logits, key, temps) + 1) % logits.shape[-1]

    put(engine, "_sample_tokens", broken)


FAULTS = {f.__name__: f for f in (state_unchanged, half_batch,
                                  token_altered)}
