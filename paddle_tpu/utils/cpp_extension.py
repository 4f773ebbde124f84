"""``paddle.utils.cpp_extension`` — user custom C++ ops
(``python/paddle/utils/cpp_extension/`` parity).

TPU-first pipeline: the user kernel is host C++ over ``PTE_Tensor``
views (``native/include/paddle_tpu_ext.h``, the ``paddle/extension.h``
counterpart). ``load()`` compiles it with g++, enumerates the ops its
constructor-registered table exports, and wraps each as a framework op:
eager calls run the kernel directly on numpy views; under ``jax.jit``
the op lowers through ``jax.pure_callback`` so custom ops compose with
the compile path (the reference achieves the same via its custom-op
→ PHI registration). Backward: pass ``backward_op=`` when calling, or
wire a PyLayer on top.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import sys
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

__all__ = ["load", "get_include", "CppExtension", "CUDAExtension",
           "BuildExtension", "setup", "CustomOpModule"]

_PKG_DIR = os.path.dirname(os.path.abspath(__file__))
_REPO_ROOT = os.path.dirname(os.path.dirname(_PKG_DIR))
_INCLUDE_DIR = os.path.join(_REPO_ROOT, "native", "include")

_DTYPE_CODES = {
    np.dtype(np.float32): 0, np.dtype(np.float64): 1,
    np.dtype(np.int32): 2, np.dtype(np.int64): 3,
    np.dtype(np.bool_): 4, np.dtype(np.uint8): 5,
    np.dtype(np.int8): 6, np.dtype(np.float16): 7,
}


def get_include() -> str:
    return _INCLUDE_DIR


class _PTETensor(ctypes.Structure):
    _fields_ = [("data", ctypes.c_void_p),
                ("shape", ctypes.POINTER(ctypes.c_int64)),
                ("ndim", ctypes.c_int32),
                ("dtype", ctypes.c_int32)]


def _make_view(arr: np.ndarray, shapes_keepalive: list) -> _PTETensor:
    shape = (ctypes.c_int64 * arr.ndim)(*arr.shape)
    shapes_keepalive.append(shape)
    return _PTETensor(
        data=arr.ctypes.data_as(ctypes.c_void_p), shape=shape,
        ndim=arr.ndim, dtype=_DTYPE_CODES[arr.dtype])


class CustomOp:
    """One registered op from a user library, callable on Tensors."""

    def __init__(self, lib, index: int, name: str, n_outputs: int):
        self._lib = lib
        self._index = index
        self.name = name
        self.n_outputs = n_outputs
        # default InferShape: outputs mirror input 0 (reference default
        # for unary-like ops); override via set_shape_fn
        self._shape_fn: Optional[Callable] = None

    def set_shape_fn(self, fn: Callable):
        """fn(*input_(shape, dtype) pairs) -> list of (shape, dtype)."""
        self._shape_fn = fn
        return self

    def _out_specs(self, arrays: Sequence[np.ndarray]):
        if self._shape_fn is not None:
            return self._shape_fn(*[(a.shape, a.dtype) for a in arrays])
        return [(arrays[0].shape, arrays[0].dtype)] * self.n_outputs

    def _run_host(self, *arrays: np.ndarray):
        arrays = [np.ascontiguousarray(a) for a in arrays]
        outs = [np.empty(s, d) for s, d in self._out_specs(arrays)]
        keep: list = []
        in_views = (_PTETensor * max(len(arrays), 1))(
            *[_make_view(a, keep) for a in arrays])
        out_views = (_PTETensor * max(len(outs), 1))(
            *[_make_view(o, keep) for o in outs])
        self._lib.pte_op_call(self._index, in_views, len(arrays),
                              out_views, len(outs))
        return outs

    def __call__(self, *tensors):
        import jax
        from ..framework.core import as_jax, _wrap_out

        arrays = [as_jax(t) if hasattr(t, "_data") else t
                  for t in tensors]
        traced = any(isinstance(a, jax.core.Tracer) for a in arrays)
        if not traced:
            # eager: run the host kernel directly on numpy views (no
            # runtime callback needed)
            outs = self._run_host(*[np.asarray(a) for a in arrays])
            wrapped = tuple(_wrap_out(jax.numpy.asarray(o))
                            for o in outs)
            return wrapped if len(wrapped) > 1 else wrapped[0]

        # under jit: lower through pure_callback so the custom op stays
        # inside the compiled program (reference: custom op → PHI
        # registration keeps it inside the executor graph)
        out_specs = self._out_specs(
            [np.empty(a.shape, a.dtype) for a in arrays])
        result_sds = [jax.ShapeDtypeStruct(s, d) for s, d in out_specs]

        def cb(*np_arrays):
            return tuple(self._run_host(
                *[np.asarray(x) for x in np_arrays]))

        out = jax.pure_callback(cb, tuple(result_sds), *arrays)
        wrapped = tuple(_wrap_out(o) for o in out)
        return wrapped if len(wrapped) > 1 else wrapped[0]


class CustomOpModule:
    """Namespace holding every op a user library registered."""

    def __init__(self, name: str, lib_path: str):
        self.__name__ = name
        self._lib_path = lib_path
        lib = ctypes.CDLL(lib_path)
        lib.pte_num_ops.restype = ctypes.c_int
        lib.pte_op_name.restype = ctypes.c_char_p
        lib.pte_op_name.argtypes = [ctypes.c_int]
        lib.pte_op_n_outputs.restype = ctypes.c_int
        lib.pte_op_n_outputs.argtypes = [ctypes.c_int]
        lib.pte_op_call.argtypes = [
            ctypes.c_int, ctypes.POINTER(_PTETensor), ctypes.c_int,
            ctypes.POINTER(_PTETensor), ctypes.c_int]
        self._ops: Dict[str, CustomOp] = {}
        for i in range(lib.pte_num_ops()):
            op_name = lib.pte_op_name(i).decode()
            op = CustomOp(lib, i, op_name, lib.pte_op_n_outputs(i))
            self._ops[op_name] = op
            setattr(self, op_name, op)

    def op_names(self) -> List[str]:
        return list(self._ops)


def _build_dir() -> str:
    d = os.path.join(_REPO_ROOT, "paddle_tpu", "native", "_lib",
                     "extensions")
    os.makedirs(d, exist_ok=True)
    return d


def load(name: str, sources: Sequence[str], extra_cxx_flags=None,
         extra_cuda_cflags=None, extra_include_paths=None,
         extra_library_paths=None, extra_libraries=None,
         build_directory=None, verbose=False, **kwargs) -> CustomOpModule:
    """JIT-compile user sources and return a module of their ops
    (``paddle.utils.cpp_extension.load`` parity; CUDA args accepted and
    ignored — kernels are host C++ on the TPU build)."""
    sources = [os.path.abspath(s) for s in sources]
    out_dir = build_directory or _build_dir()
    tag = hashlib.sha1("|".join(sources).encode()).hexdigest()[:10]
    lib_path = os.path.join(out_dir, f"lib{name}_{tag}.so")
    src_mtime = max(os.path.getmtime(s) for s in sources)
    if (not os.path.exists(lib_path)
            or os.path.getmtime(lib_path) < src_mtime):
        cmd = ["g++", "-O2", "-std=c++17", "-shared", "-fPIC",
               f"-I{_INCLUDE_DIR}"]
        for p in (extra_include_paths or []):
            cmd.append(f"-I{p}")
        cmd += list(extra_cxx_flags or [])
        cmd += ["-o", lib_path, *sources]
        for p in (extra_library_paths or []):
            cmd.append(f"-L{p}")
        for l in (extra_libraries or []):
            cmd.append(f"-l{l}")
        if verbose:
            print("[cpp_extension]", " ".join(cmd), file=sys.stderr)
        subprocess.run(cmd, check=True, capture_output=not verbose)
    return CustomOpModule(name, lib_path)


# --- setuptools-style API (reference parity; thin over load) -------------

class CppExtension:
    def __init__(self, sources, *args, **kwargs):
        self.sources = sources
        self.kwargs = kwargs


CUDAExtension = CppExtension  # CUDA sources are not applicable on TPU


class BuildExtension:
    @staticmethod
    def with_options(**options):
        return BuildExtension


def setup(name: str, ext_modules=None, **kwargs):
    """Builds immediately (no setuptools machinery needed for JIT use)."""
    exts = ext_modules if isinstance(ext_modules, (list, tuple)) \
        else [ext_modules]
    mods = []
    for ext in exts:
        if ext is None:
            continue
        mods.append(load(name, ext.sources, **ext.kwargs))
    return mods[0] if len(mods) == 1 else mods
