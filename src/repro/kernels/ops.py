"""Jit-friendly kernel dispatch.

Each op has (up to) three implementations:
  * ``xla``              — the pure-jnp oracle from ``ref.py`` (default on CPU
                           and for the SPMD dry-run).
  * ``pallas``           — the TPU kernel (``pl.pallas_call`` + BlockSpec).
  * ``pallas_interpret`` — the same kernel body executed in interpret mode
                           (CPU correctness validation; used by tests).

Selection: explicit ``impl=`` argument > ``set_default_impl()`` > backend
default (``pallas`` on TPU, ``xla`` elsewhere).

The Pallas kernels are forward-only: differentiating through one raises
``NotImplementedError`` naming the kernel (``pallas_call`` has no
transpose rule, and a silent fall-back to ``xla`` would train through a
different code path than the one the chip runs).
"""
from __future__ import annotations

import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ref

_DEFAULT_IMPL: Optional[str] = None
_VALID = ("xla", "pallas", "pallas_interpret")


def set_default_impl(impl: Optional[str]) -> None:
    global _DEFAULT_IMPL
    if impl is not None and impl not in _VALID:
        raise ValueError(f"impl must be one of {_VALID}, got {impl!r}")
    _DEFAULT_IMPL = impl


def resolve_impl(impl: Optional[str] = None) -> str:
    if impl is not None:
        return impl
    if _DEFAULT_IMPL is not None:
        return _DEFAULT_IMPL
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def _forward_only(name: str, kernel: Callable) -> Callable:
    """``kernel`` with a backward pass that raises, naming the kernel."""

    @jax.custom_vjp
    def f(*args):
        return kernel(*args)

    def fwd(*args):
        return kernel(*args), None

    def bwd(_, g):
        raise NotImplementedError(
            f"the {name} Pallas kernel has no backward pass: training "
            f"through it (the default on a TPU) is not supported yet; "
            f"call repro.kernels.ops.set_default_impl('xla') to train "
            f"through the jnp reference instead")

    f.defvjp(fwd, bwd)
    return f


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, sliding_window: int = 0,
                    impl: Optional[str] = None) -> jnp.ndarray:
    """q (B,S,Hq,d), k/v (B,L,Hkv,d) -> (B,S,Hq,d)."""
    which = resolve_impl(impl)
    if which == "xla":
        return ref.attention_ref(q, k, v, causal=causal,
                                 sliding_window=sliding_window)
    from repro.kernels import flash_attention as fa
    return _forward_only("flash_attention", functools.partial(
        fa.flash_attention, causal=causal, sliding_window=sliding_window,
        interpret=(which == "pallas_interpret")))(q, k, v)


# ---------------------------------------------------------------------------
# Mamba2 SSD
# ---------------------------------------------------------------------------

def ssd(x: jnp.ndarray, log_decay: jnp.ndarray, b: jnp.ndarray, c: jnp.ndarray,
        *, chunk: int = 64, initial_state: Optional[jnp.ndarray] = None,
        impl: Optional[str] = None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    which = resolve_impl(impl)
    if which == "xla":
        return ref.ssd_chunked_ref(x, log_decay, b, c, chunk=chunk,
                                   initial_state=initial_state)
    from repro.kernels import ssd_scan
    return _forward_only("ssd", lambda x, a, b, c, s0: ssd_scan.ssd(
        x, a, b, c, chunk=chunk, initial_state=s0,
        interpret=(which == "pallas_interpret")))(x, log_decay, b, c,
                                                  initial_state)


def ssd_decode(state: jnp.ndarray, x: jnp.ndarray, log_decay: jnp.ndarray,
               b: jnp.ndarray, c: jnp.ndarray
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Single-step SSD recurrence (pure jnp everywhere — O(1) work)."""
    return ref.ssd_decode_step(state, x, log_decay, b, c)


# ---------------------------------------------------------------------------
# RWKV6 WKV
# ---------------------------------------------------------------------------

WKV6_MIN_KERNEL_CHUNK = 64   # Pallas kernel tiles the sequence in 64 lanes


def wkv6_effective_chunk(chunk: int, impl: Optional[str] = None) -> int:
    """The chunk size ``wkv6`` actually runs with under ``impl``.

    The Pallas kernel requires sequence tiles of at least
    ``WKV6_MIN_KERNEL_CHUNK`` lanes, so smaller requests are coerced up
    (the WKV recurrence is chunk-size invariant — only the memory/latency
    trade moves).  The xla reference honors the request exactly.
    """
    which = resolve_impl(impl)
    if which == "xla":
        return chunk
    return max(chunk, WKV6_MIN_KERNEL_CHUNK)


def wkv6(r: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, log_w: jnp.ndarray,
         u: jnp.ndarray, *, chunk: int = 16,
         initial_state: Optional[jnp.ndarray] = None,
         impl: Optional[str] = None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``chunk`` is a request: the Pallas paths run with
    ``wkv6_effective_chunk(chunk, impl)`` (coerced up to the kernel's
    64-lane minimum tile), the xla path with ``chunk`` as given."""
    which = resolve_impl(impl)
    if which == "xla":
        return ref.wkv6_chunked_ref(r, k, v, log_w, u, chunk=chunk,
                                    initial_state=initial_state)
    from repro.kernels import wkv6 as wkv6_kernel
    return _forward_only("wkv6", lambda r, k, v, lw, u, s0: wkv6_kernel.wkv6(
        r, k, v, lw, u, chunk=wkv6_effective_chunk(chunk, which),
        initial_state=s0, interpret=(which == "pallas_interpret")))(
            r, k, v, log_w, u, initial_state)


def wkv6_decode(state: jnp.ndarray, r: jnp.ndarray, k: jnp.ndarray,
                v: jnp.ndarray, log_w: jnp.ndarray, u: jnp.ndarray
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    return ref.wkv6_decode_step(state, r, k, v, log_w, u)
