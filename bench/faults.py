"""Faults planted in the served path, for the checks that must fail.

Each fault is a function of a new ``ServeEngine``, which it alters
before the engine's first step; ``planted(fault)`` applies it to every
engine made inside the ``with``.  ``bench/tests`` plants them at a tiny
size on the CPU, ``bench/tools/fault_readings.py`` at a cell's own size
and load on the chip.

- ``kv_unwritten``: the decode step never writes its new K/V row, so
  attention reads whatever the cache held at and after the prompt.  It
  changes only the traced step, so it runs where the engine donates its
  cache, on the chip too.
- ``state_unchanged``: the decode step attends over its new K/V but
  hands back the cache it was given, so each step loses the rows of the
  steps before it.  It needs a cache that the step does not consume:
  the CPU's.
- ``half_batch``: half the live slots get the first live slot's logits.
- ``token_altered``: every third sampled token is the next one up.
"""
from __future__ import annotations

import contextlib

import numpy as np


def kv_unwritten(serve) -> None:
    import jax
    from repro.models import attention
    from repro.serve import engine as eng
    model, impl = serve.model, serve.impl

    def decode_step(p, c, t, pos):
        # swapped while the step traces, on its first call
        attn_decode = attention.attn_decode
        attention.attn_decode = _attn_decode_unwritten
        try:
            return model.decode_step(p, c, t, pos, impl=impl)
        finally:
            attention.attn_decode = attn_decode
    donate = eng.donates(serve.device)
    serve._decode_jit = jax.jit(decode_step,
                                donate_argnums=(1,) if donate else ())


def state_unchanged(serve) -> None:
    from repro.serve import engine as eng
    if eng.donates(serve.device):
        raise RuntimeError("state_unchanged needs an engine that keeps "
                           "its input cache: the CPU's")
    decode = serve._decode_jit

    def step(p, c, t, pos):
        logits, _ = decode(p, c, t, pos)
        return logits, c                  # the cache is never written
    serve._decode_jit = step


def half_batch(serve) -> None:
    decode = serve._decode_jit

    def step(p, c, t, pos):
        logits, nc = decode(p, c, t, pos)
        live = [i for i, r in enumerate(serve.slot_req)
                if r is not None and i not in serve._prefill]
        rows = np.arange(logits.shape[0])
        rows[live[len(live) // 2:]] = live[0] if live else 0
        return logits[rows], nc
    serve._decode_jit = step


def token_altered(serve) -> None:
    sample = serve._sample
    vocab = serve.model.cfg.vocab
    calls = [0]

    def altered(logits, req):
        calls[0] += 1
        tok = sample(logits, req)
        return (tok + 1) % vocab if calls[0] % 3 == 0 else tok
    serve._sample = altered


FAULTS = {f.__name__: f for f in (kv_unwritten, state_unchanged,
                                  half_batch, token_altered)}


def _attn_decode_unwritten(cfg, p, x, cache, pos, *, kind="attn",
                           layer=None, impl="auto"):
    """``attention.attn_decode`` without its two K/V writes."""
    from repro.kernels import ops
    from repro.models import attention as a
    q = a._project_q(cfg, p, x, pos[:, None], kind)
    k, v = cache["k"], cache["v"]
    at = layer
    if layer is None:
        k, v, at = k[None], v[None], 0
    window = cfg.window if kind == "local" else 0
    o = ops.decode_attention(q[:, 0], k, v, pos, at, window=window,
                             softcap=cfg.attn_softcap, scale=cfg.attn_scale,
                             impl=impl)
    return a._out(cfg, p, o[:, None]), cache


@contextlib.contextmanager
def planted(fault):
    """Every ``ServeEngine`` made inside the ``with`` runs ``fault``."""
    from repro.serve import engine as eng
    init = eng.ServeEngine.__init__

    def patched(self, *a, **kw):
        init(self, *a, **kw)
        fault(self)

    eng.ServeEngine.__init__ = patched
    try:
        yield
    finally:
        eng.ServeEngine.__init__ = init
