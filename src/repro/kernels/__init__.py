"""Kernel registry: one place that answers "pallas or lax?".

Config switches (``ModelConfig.attention_impl``) and backend plan hooks
(``RingBackend``'s wire-kernel selection) both route through this registry
instead of importing kernel modules ad hoc.  Registration is lazy —
targets are ``"module:attr"`` strings resolved on first use — so importing
:mod:`repro.kernels` never drags in Pallas, and kernel packages can import
the registry without a cycle.

Selection contract (mirrors the backend plan hooks): the *caller* names a
kernel, :func:`kernel_mode` says whether the Pallas variant can run on this
platform (interpret mode on CPU, real lowering on TPU/GPU), and
:func:`resolve` hands back the callable with ``interpret=`` pre-bound — or
the registered lax fallback when Pallas is unavailable.
"""
from __future__ import annotations

import functools
import importlib
from typing import Any, Callable, Optional

import jax

#: ring-wire quantization granule and TPU lane width: one int8 absmax scale
#: per 128 wire elements.  Defined here, in the Pallas-free registry, so
#: layout code (the ZeRO-1 flat padding) can align to it without importing
#: the kernels.
WIRE_BLOCK = 128

#: name -> variant ("pallas" | "lax") -> lazy "module[:attr]" target
_REGISTRY: dict[str, dict[str, Any]] = {}

#: platforms where the pallas variant is usable (cpu via interpret mode)
_PALLAS_PLATFORMS = ("cpu", "tpu", "gpu")


def register(name: str, variant: str, target: Any) -> None:
    """Register a kernel implementation.  ``target`` is a callable or a
    lazy ``"module[:attr]"`` string resolved on first :func:`get`."""
    if variant not in ("pallas", "lax"):
        raise ValueError(f"unknown kernel variant {variant!r}")
    _REGISTRY.setdefault(name, {})[variant] = target


def _resolve_target(target: Any):
    if callable(target):
        return target
    mod_name, _, attr = str(target).partition(":")
    mod = importlib.import_module(mod_name)
    return getattr(mod, attr) if attr else mod


def get(name: str, variant: str):
    """The registered implementation (callable or module), resolved lazily;
    None if absent."""
    target = _REGISTRY.get(name, {}).get(variant)
    if target is None:
        return None
    fn = _resolve_target(target)
    _REGISTRY[name][variant] = fn  # cache the resolved object
    return fn


def _platform(platform: Optional[str]) -> str:
    return platform or jax.default_backend()


def interpret_on(platform: Optional[str] = None) -> bool:
    """Pallas interpret mode: on for CPU (tests/CI), off on TPU/GPU."""
    return _platform(platform) == "cpu"


def kernel_mode(name: str, platform: Optional[str] = None) -> str:
    """``"pallas"`` iff ``name`` has a Pallas variant runnable on this
    platform, else ``"lax"`` — the value surfaced per ABI entry as
    ``capabilities()[entry]["wire_kernel"]`` by kernel-backed backends."""
    if name in _REGISTRY and "pallas" in _REGISTRY[name] \
            and _platform(platform) in _PALLAS_PLATFORMS:
        return "pallas"
    return "lax"


def resolve(name: str, platform: Optional[str] = None):
    """-> ``(mode, fn)``: the best implementation for this platform.

    ``mode`` is ``"pallas"`` or ``"lax"``; Pallas *callables* come with
    ``interpret=`` pre-bound for the platform (module targets — op
    families like ``ring_wire`` — are returned as-is).  ``(None, None)``
    when nothing is registered under ``name``.
    """
    mode = kernel_mode(name, platform)
    fn = get(name, mode)
    if fn is None and mode == "pallas":  # pallas leg absent at runtime
        mode, fn = "lax", get(name, "lax")
    if fn is None:
        return None, None
    if mode == "pallas" and callable(fn):
        fn = functools.partial(fn, interpret=interpret_on(platform))
    return mode, fn


# -- built-in kernels (lazy: nothing imports until first resolve) -----------
register("flash_attention", "pallas",
         "repro.kernels.flash_attention.ops:flash_mha")
register("ring_wire", "pallas", "repro.kernels.ring_wire.ops")
register("mamba2_ssd", "pallas", "repro.kernels.mamba2_ssd.ops:ssd_apply")
register("rwkv6_scan", "pallas", "repro.kernels.rwkv6_scan.ops:wkv6_apply")
