"""Shared backend selection for the Pallas kernel tier.

Both kernel families (``gru_scan``, ``ssd``) need the same decisions:

* ``on_tpu()``      — is the default JAX backend a real TPU?
* ``interpret()``   — should ``pallas_call`` run in interpret mode?  True
  off-TPU (CPU containers, CI) so the same kernel source stays executable
  everywhere; on TPU the Mosaic compiler always takes over.
* ``pallas_backward()`` — should the *backward* pass use the hand-written
  Pallas kernel (True on TPU) or the pure-jnp residual reverse scan (the
  off-TPU default, which is faster than interpret-mode emulation on CPU)?

The ``REPRO_PALLAS_INTERPRET`` environment variable acts only off-TPU: it
routes the backward pass through the interpret-mode Pallas kernels too, so
CI exercises them without TPU hardware.  On a TPU it changes nothing — a
kernel there is compiled, never interpreted behind the caller's back.  It
is read at trace time, so set it before the first jit.
"""

from __future__ import annotations

import os

import jax

_ENV_INTERPRET = "REPRO_PALLAS_INTERPRET"
_TRUTHY = frozenset({"1", "true", "yes", "on"})


def _env_forced() -> bool:
    return os.environ.get(_ENV_INTERPRET, "").strip().lower() in _TRUTHY


def on_tpu() -> bool:
    """True when the default JAX backend is a real TPU."""
    return jax.default_backend() == "tpu"


def interpret() -> bool:
    """Interpret-mode flag for ``pallas_call``: True exactly when off-TPU."""
    return not on_tpu()


def resolve_interpret(flag: bool | None) -> bool:
    """A kernel's ``interpret`` argument: ``None`` means :func:`interpret`."""
    return interpret() if flag is None else bool(flag)


def pallas_backward() -> bool:
    """Route the backward pass through the Pallas backward kernel?

    True on TPU (compiled Mosaic), or off-TPU when ``REPRO_PALLAS_INTERPRET``
    asks for interpret-mode coverage; otherwise False and the pure-jnp
    residual reverse scan runs instead.
    """
    return on_tpu() or _env_forced()
