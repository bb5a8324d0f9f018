"""Datatype registry: ABI datatype handles <-> jnp dtypes, and the two
``type_size`` strategies the paper benchmarks (§6.1).

* :func:`type_size_encoded` — MPICH-style: extract the size from the handle
  bits (fixed-size types only; falls back to the table for variable-size).
* :func:`type_size_lookup` — Open-MPI-style: always go through an object
  table (the 352-byte-struct pointer chase of §3.3, modelled as a dict of
  descriptor objects).

Both must agree everywhere; the benchmark ``benchmarks/bench_type_size.py``
reproduces the paper's measurement that the two are equally negligible.

Derived datatypes (``type_contiguous``/``type_vector``) allocate user handles
above the zero page and register descriptors, giving the Mukautuva layer a
nontrivial conversion job (the paper's alltoallw worst case needs vectors of
derived types).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from . import handles as H
from .errors import PAX_ERR_TYPE, PaxError

try:  # jax is required by the framework but keep this module importable alone
    import jax.numpy as jnp

    _JNP = True
except Exception:  # pragma: no cover
    jnp = None
    _JNP = False


@dataclasses.dataclass(frozen=True)
class TypeDescriptor:
    """The IMPL-side object an Open-MPI-style backend would point to."""

    handle: int
    name: str
    size: int  # bytes; element size * count for derived types
    numpy_dtype: Optional[np.dtype]
    count: int = 1  # elements (for contiguous/vector derived types)
    base: Optional[int] = None  # base type handle for derived types


def _np(name: str) -> np.dtype:
    return np.dtype(name)


# ---------------------------------------------------------------------------
# Predefined datatype table. Sizes of variable-size C types follow the A64O64
# LP64 platform model (§5.1): int=4, long=8, size-like=8.
# ---------------------------------------------------------------------------
_PREDEFINED: dict[int, TypeDescriptor] = {}


def _register(handle: int, name: str, size: int, np_dtype: Optional[np.dtype]) -> None:
    _PREDEFINED[handle] = TypeDescriptor(handle, name, size, np_dtype)


_register(H.PAX_DATATYPE_NULL, "PAX_DATATYPE_NULL", 0, None)
# variable-size C types (size from table, never from bits)
_register(H.PAX_AINT, "PAX_AINT", 8, _np("int64"))
_register(H.PAX_COUNT, "PAX_COUNT", 8, _np("int64"))
_register(H.PAX_OFFSET, "PAX_OFFSET", 8, _np("int64"))
_register(H.PAX_PACKED, "PAX_PACKED", 1, _np("uint8"))
_register(H.PAX_SHORT, "PAX_SHORT", 2, _np("int16"))
_register(H.PAX_INT, "PAX_INT", 4, _np("int32"))
_register(H.PAX_LONG, "PAX_LONG", 8, _np("int64"))
_register(H.PAX_LONG_LONG, "PAX_LONG_LONG", 8, _np("int64"))
_register(H.PAX_UNSIGNED_SHORT, "PAX_UNSIGNED_SHORT", 2, _np("uint16"))
_register(H.PAX_UNSIGNED_INT, "PAX_UNSIGNED_INT", 4, _np("uint32"))
_register(H.PAX_UNSIGNED_LONG, "PAX_UNSIGNED_LONG", 8, _np("uint64"))
_register(H.PAX_UNSIGNED_LONG_LONG, "PAX_UNSIGNED_LONG_LONG", 8, _np("uint64"))
_register(H.PAX_FLOAT, "PAX_FLOAT", 4, _np("float32"))
_register(H.PAX_DOUBLE, "PAX_DOUBLE", 8, _np("float64"))
_register(H.PAX_LONG_DOUBLE, "PAX_LONG_DOUBLE", 8, _np("float64"))
_register(H.PAX_C_BOOL, "PAX_C_BOOL", 1, _np("bool"))
# fixed-size types (size ALSO encoded in bits 3..5; table must agree)
_register(H.PAX_INT8_T, "PAX_INT8_T", 1, _np("int8"))
_register(H.PAX_UINT8_T, "PAX_UINT8_T", 1, _np("uint8"))
_register(H.PAX_CHAR, "PAX_CHAR", 1, _np("int8"))
_register(H.PAX_SIGNED_CHAR, "PAX_SIGNED_CHAR", 1, _np("int8"))
_register(H.PAX_UNSIGNED_CHAR, "PAX_UNSIGNED_CHAR", 1, _np("uint8"))
_register(H.PAX_BYTE, "PAX_BYTE", 1, _np("uint8"))
_register(H.PAX_INT16_T, "PAX_INT16_T", 2, _np("int16"))
_register(H.PAX_UINT16_T, "PAX_UINT16_T", 2, _np("uint16"))
_register(H.PAX_FLOAT16, "PAX_FLOAT16", 2, _np("float16"))
_register(H.PAX_INT32_T, "PAX_INT32_T", 4, _np("int32"))
_register(H.PAX_UINT32_T, "PAX_UINT32_T", 4, _np("uint32"))
_register(H.PAX_FLOAT32, "PAX_FLOAT32", 4, _np("float32"))
_register(H.PAX_INT64_T, "PAX_INT64_T", 8, _np("int64"))
_register(H.PAX_UINT64_T, "PAX_UINT64_T", 8, _np("uint64"))
_register(H.PAX_FLOAT64, "PAX_FLOAT64", 8, _np("float64"))
_register(H.PAX_COMPLEX64, "PAX_COMPLEX64", 8, _np("complex64"))
_register(H.PAX_COMPLEX128, "PAX_COMPLEX128", 16, _np("complex128"))

# TPU extension dtypes, allocated in reserved fixed-size slots (DESIGN.md §1.4)
if _JNP:
    _register(H.PAX_BFLOAT16, "PAX_BFLOAT16", 2, np.dtype(jnp.bfloat16))
    _register(H.PAX_FLOAT8_E4M3, "PAX_FLOAT8_E4M3", 1, np.dtype(jnp.float8_e4m3fn))
    _register(H.PAX_FLOAT8_E5M2, "PAX_FLOAT8_E5M2", 1, np.dtype(jnp.float8_e5m2))

N_PREDEFINED = len(_PREDEFINED)

# dtype -> canonical handle (first registration wins for aliases like CHAR)
_NP_TO_HANDLE: dict[np.dtype, int] = {}
for _h, _d in sorted(_PREDEFINED.items()):
    if _d.numpy_dtype is not None and _d.numpy_dtype not in _NP_TO_HANDLE:
        # prefer fixed-size canonical handles for numpy-visible dtypes
        _NP_TO_HANDLE[_d.numpy_dtype] = _h
# canonical overrides: fixed-size handles win over C aliases
for _h in (
    H.PAX_INT8_T,
    H.PAX_UINT8_T,
    H.PAX_INT16_T,
    H.PAX_UINT16_T,
    H.PAX_INT32_T,
    H.PAX_UINT32_T,
    H.PAX_INT64_T,
    H.PAX_UINT64_T,
    H.PAX_FLOAT16,
    H.PAX_FLOAT32,
    H.PAX_FLOAT64,
    H.PAX_COMPLEX64,
    H.PAX_COMPLEX128,
):
    _NP_TO_HANDLE[_PREDEFINED[_h].numpy_dtype] = _h
if _JNP:
    _NP_TO_HANDLE[np.dtype(jnp.bfloat16)] = H.PAX_BFLOAT16


class DatatypeRegistry:
    """Predefined + derived datatype registry.

    One instance per ABI context; derived types allocate user handles above
    the zero page (``handles.make_user_handle``).
    """

    def __init__(self) -> None:
        self._derived: dict[int, TypeDescriptor] = {}
        self._next_index = 0

    # -- queries ------------------------------------------------------------

    def descriptor(self, handle: int) -> TypeDescriptor:
        desc = _PREDEFINED.get(handle)
        if desc is None:
            desc = self._derived.get(handle)
        if desc is None:
            raise PaxError(PAX_ERR_TYPE, H.describe(handle))
        return desc

    def type_size_encoded(self, handle: int) -> int:
        """MPICH-style: bit extraction for fixed-size types (§3.3/§6.1)."""
        if H.datatype_is_fixed_size(handle):
            return H.datatype_encoded_size(handle)
        return self.descriptor(handle).size

    def type_size_lookup(self, handle: int) -> int:
        """Open-MPI-style: always dereference the descriptor (§3.3/§6.1)."""
        return self.descriptor(handle).size

    type_size = type_size_encoded  # ABI default

    def to_numpy_dtype(self, handle: int) -> np.dtype:
        d = self.descriptor(handle)
        if d.numpy_dtype is None:
            raise PaxError(PAX_ERR_TYPE, f"{d.name} has no array dtype")
        return d.numpy_dtype

    def from_array(self, array) -> int:
        """Infer the canonical ABI datatype handle from an array's dtype."""
        dt = np.dtype(array.dtype)
        try:
            return _NP_TO_HANDLE[dt]
        except KeyError:
            raise PaxError(PAX_ERR_TYPE, f"no ABI datatype for dtype {dt}") from None

    # -- derived types (gives Mukautuva real conversion work) ---------------

    def type_contiguous(self, count: int, base: int) -> int:
        H.check_handle(base, H.HandleKind.DATATYPE)
        bdesc = self.descriptor(base)
        handle = H.make_user_handle(H.HandleKind.DATATYPE, self._next_index)
        self._next_index += 1
        self._derived[handle] = TypeDescriptor(
            handle,
            f"contig({count},{bdesc.name})",
            bdesc.size * count,
            bdesc.numpy_dtype,
            count=count * bdesc.count,
            base=base,
        )
        return handle

    def type_vector(self, count: int, blocklength: int, stride: int, base: int) -> int:
        H.check_handle(base, H.HandleKind.DATATYPE)
        bdesc = self.descriptor(base)
        handle = H.make_user_handle(H.HandleKind.DATATYPE, self._next_index)
        self._next_index += 1
        self._derived[handle] = TypeDescriptor(
            handle,
            f"vector({count},{blocklength},{stride},{bdesc.name})",
            bdesc.size * count * blocklength,
            bdesc.numpy_dtype,
            count=count * blocklength * bdesc.count,
            base=base,
        )
        return handle

    def type_free(self, handle: int) -> None:
        self._derived.pop(handle, None)


def predefined_descriptors() -> dict[int, TypeDescriptor]:
    return dict(_PREDEFINED)
