"""Jit'd public wrappers for the kernel package with backend dispatch.

Backends:
  * ``jnp``        — the pure-jnp oracle in ``ref.py`` (CPU, dry-run, GSPMD).
  * ``pallas``     — the TPU Pallas kernels (compiled, TPU target).
  * ``interpret``  — Pallas kernels executed with ``interpret=True`` (CPU
                     correctness validation of the kernel bodies).

The backend follows the platform the wrappers are traced on: ``pallas`` when
JAX's default backend is a TPU, ``jnp`` otherwise.  There is no fallback: on
a TPU, a kernel the compiler refuses raises.  Tests pin a backend with
``backend(...)`` (for example ``interpret`` to run the Pallas bodies on the
CPU); the choice is read while tracing, so a jitted function keeps the
backend it was first traced under.
"""
from __future__ import annotations

import contextlib
from typing import Optional

import jax

from . import ref

_BACKENDS = ("jnp", "pallas", "interpret")
_BACKEND: Optional[str] = None       # None: follow the platform


def set_backend(name: Optional[str]) -> None:
    """Pin a backend (tests only); ``None`` returns to the platform's."""
    global _BACKEND
    if name is not None and name not in _BACKENDS:
        raise ValueError(f"unknown kernel backend {name!r}")
    _BACKEND = name


def get_backend() -> str:
    if _BACKEND is not None:
        return _BACKEND
    return "pallas" if jax.default_backend() == "tpu" else "jnp"


@contextlib.contextmanager
def backend(name: str):
    prev = _BACKEND
    set_backend(name)
    try:
        yield
    finally:
        set_backend(prev)


def _pallas_mod():
    from . import flash_attention, decode_attention, ssd_scan, rglru_scan
    return flash_attention, decode_attention, ssd_scan, rglru_scan


# ---------------------------------------------------------------------------


def mha(q, k, v, *, causal=True, window=0, softcap=0.0, scale=None,
        q_offset=0, q_chunk=0, unroll=False):
    be = get_backend()
    if be == "jnp":
        return ref.mha(q, k, v, causal=causal, window=window, softcap=softcap,
                       scale=scale, q_offset=q_offset, q_chunk=q_chunk,
                       unroll=unroll)
    fa, *_ = _pallas_mod()
    return fa.flash_attention(
        q, k, v, causal=causal, window=window, softcap=softcap, scale=scale,
        q_offset=q_offset, interpret=(be == "interpret"))


def decode_attention(q, k_cache, v_cache, lengths, *, layer=None,
                     softcap=0.0, scale=None, window=0):
    """Caches (B,Smax,K,D), or a layer stack (L,B,Smax,K,D) and ``layer``."""
    be = get_backend()
    if be == "jnp":
        return ref.decode_attention(q, k_cache, v_cache, lengths, layer=layer,
                                    softcap=softcap, scale=scale,
                                    window=window)
    _, da, *_ = _pallas_mod()
    return da.decode_attention(
        q, k_cache, v_cache, lengths, layer=layer, softcap=softcap,
        scale=scale, window=window, interpret=(be == "interpret"))


def ssd(x, dt, A, Bm, Cm, D=None, *, chunk=256, init_state=None,
        unroll=False):
    be = get_backend()
    if be == "jnp":
        return ref.ssd(x, dt, A, Bm, Cm, D, chunk=chunk,
                       init_state=init_state, unroll=unroll)
    *_, ssd_k, _ = _pallas_mod()
    return ssd_k.ssd_scan(x, dt, A, Bm, Cm, D, chunk=chunk,
                          init_state=init_state,
                          interpret=(be == "interpret"))


def ssd_decode(x, dt, A, Bm, Cm, D, state):
    # Single recurrent step: einsum-bound, no kernel needed.
    return ref.ssd_decode(x, dt, A, Bm, Cm, D, state)


def rglru(a, b, h0=None):
    be = get_backend()
    if be == "jnp":
        return ref.rglru(a, b, h0)
    *_, rk = _pallas_mod()
    return rk.rglru_scan(a, b, h0, interpret=(be == "interpret"))


def rglru_decode(a, b, h):
    return ref.rglru_decode(a, b, h)
