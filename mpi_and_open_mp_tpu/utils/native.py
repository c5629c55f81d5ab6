"""ctypes bindings to the native C++ library (``native/``).

``native/lifeio.cpp``, built as ``liblifeio.so``, holds the config
parser (the reference's ``3-life/life2d.c:52-72`` loader) and two serial
Life oracles. VTK frames are written by ``utils/vtk.py`` in NumPy.
Python falls back transparently when the library hasn't been built
(``make -C native``). Under a NON-editable install the repo-relative
default can't resolve — set ``MOMP_NATIVE_LIB=/path/to/liblifeio.so``
(the fast path is optional either way).
"""

from __future__ import annotations

import ctypes
import os

import numpy as np

_LIB = None
_TRIED = False

# Default resolution assumes the module lives in the repo tree (in-place
# use or an editable install); a NON-editable install has no native/
# sibling, so MOMP_NATIVE_LIB points at the built .so explicitly there.
_HERE = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_FROM_ENV = bool(os.environ.get("MOMP_NATIVE_LIB"))
_SO_PATH = (os.environ.get("MOMP_NATIVE_LIB")
            or os.path.join(_HERE, "native", "liblifeio.so"))


def _load():
    global _LIB, _TRIED
    if _TRIED:
        return _LIB
    _TRIED = True
    if os.environ.get("LIFE_TPU_NO_NATIVE"):
        return None
    try:
        lib = ctypes.CDLL(_SO_PATH)
        lib.lifeio_life_steps_bits  # newest symbol: reject stale builds
    except (OSError, AttributeError) as e:
        # Missing OR out-of-date library (an old .so lacking newer
        # symbols would otherwise AttributeError past this guard) —
        # fall back to the Python implementations; `make -C native`.
        # Quietly for the repo-relative default, but an EXPLICIT
        # MOMP_NATIVE_LIB that fails to load is a misconfiguration the
        # knob exists to fix — surface it instead of silently degrading.
        # (_FROM_ENV, not a live env read: _SO_PATH was frozen at import,
        # so the warning must describe the same snapshot it loaded from.)
        if _FROM_ENV:
            import warnings

            warnings.warn(
                f"MOMP_NATIVE_LIB={_SO_PATH} failed to load"
                f" ({type(e).__name__}: {e}); falling back to the Python"
                " implementations", RuntimeWarning, stacklevel=3)
        return None
    lib.lifeio_load_config.restype = ctypes.c_int
    lib.lifeio_load_config.argtypes = [
        ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_longlong),  # steps, save_steps, nx, ny, ncells
        ctypes.POINTER(ctypes.POINTER(ctypes.c_longlong)),  # cells buffer
    ]
    lib.lifeio_free.restype = None
    lib.lifeio_free.argtypes = [ctypes.POINTER(ctypes.c_longlong)]
    lib.lifeio_life_steps.restype = None
    lib.lifeio_life_steps.argtypes = [
        ctypes.POINTER(ctypes.c_uint8),
        ctypes.c_longlong,
        ctypes.c_longlong,
        ctypes.c_longlong,
    ]
    lib.lifeio_life_steps_bits.restype = None
    lib.lifeio_life_steps_bits.argtypes = lib.lifeio_life_steps.argtypes
    _LIB = lib
    return _LIB


def available() -> bool:
    return _load() is not None


def _require():
    lib = _load()
    if lib is None:
        raise RuntimeError(
            f"native lifeio library not available (expected at {_SO_PATH}):"
            " build it with `make -C native` in the repo tree, or point"
            " MOMP_NATIVE_LIB at a built liblifeio.so (required for"
            " non-editable installs, which carry no native/ sibling)"
        )
    return lib


def load_config(path):
    from mpi_and_open_mp_tpu.utils.config import LifeConfig

    lib = _require()
    header = (ctypes.c_longlong * 5)()
    cells_ptr = ctypes.POINTER(ctypes.c_longlong)()
    rc = lib.lifeio_load_config(
        str(path).encode(), header, ctypes.byref(cells_ptr)
    )
    if rc != 0:
        raise ValueError(f"{path}: native config parse failed (rc={rc})")
    steps, save_steps, nx, ny, ncells = (int(v) for v in header)
    try:
        if ncells:
            flat = np.ctypeslib.as_array(cells_ptr, shape=(ncells * 2,)).copy()
            cells = flat.reshape(-1, 2)
        else:
            cells = np.zeros((0, 2), dtype=np.int64)
    finally:
        lib.lifeio_free(cells_ptr)
    return LifeConfig(steps=steps, save_steps=save_steps, nx=nx, ny=ny, cells=cells)


def life_steps(board: np.ndarray, steps: int, bits: bool = False) -> np.ndarray:
    """Advance ``steps`` generations through the native C++ oracle.

    An independent compiled ground truth (same role as the reference's
    ``life2d`` binary) — used by tests to cross-check the NumPy oracle and
    by hosts that want a fast serial path without JAX. ``bits=True``
    selects the bit-packed (64 cells/word) carry-save variant — ~50x
    faster on big boards, itself a third independent implementation.
    """
    lib = _require()
    out = np.ascontiguousarray(board, dtype=np.uint8).copy()
    ny, nx = out.shape
    fn = lib.lifeio_life_steps_bits if bits else lib.lifeio_life_steps
    fn(out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), nx, ny, int(steps))
    return out

