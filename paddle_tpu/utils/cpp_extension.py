"""Out-of-tree custom C++ ops (reference: paddle/phi/api/ext/
op_meta_info.h:850 PD_BUILD_OP + python/paddle/utils/cpp_extension/
cpp_extension.py — setup:79 / load:797 JIT build, BuildExtension:357).

TPU-native split of the reference's custom-op story:
- custom DEVICE kernels → write Pallas (jax.experimental.pallas); they
  are jit-compiled for the MXU like the in-tree flash attention.
- custom HOST ops (pre/post-processing, tokenizers, CPU-only math) →
  this module: ``load()`` JIT-compiles C++ with the system toolchain into
  a shared library and registers each exported function as a framework op
  executed through ``jax.pure_callback`` (works eagerly and inside jit;
  the host transfer is explicit, as it would be on any accelerator).

C ABI (simplified ``PD_BUILD_OP``): each op is
``extern "C" void name(const float* in0[, const float* in1, ...],
float* out, int64_t n)`` over contiguous float32 buffers; the output has
the shape of input 0. An optional ``name_grad`` symbol with the same
arity + incoming-cotangent buffer provides the backward."""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess

import numpy as np

__all__ = ["load", "CppExtension", "setup", "get_build_directory"]


def get_build_directory():
    root = os.environ.get("PADDLE_EXTENSION_DIR",
                          os.path.join(os.path.expanduser("~"),
                                       ".cache", "paddle_tpu_extensions"))
    os.makedirs(root, exist_ok=True)
    return root


def _compile(name, sources, extra_cflags, build_directory, verbose):
    build_dir = build_directory or get_build_directory()
    os.makedirs(build_dir, exist_ok=True)
    digest = hashlib.sha1()
    for s in sources:
        with open(s, "rb") as f:
            digest.update(f.read())
    digest.update(" ".join(extra_cflags or []).encode())
    so_path = os.path.join(build_dir, f"{name}_{digest.hexdigest()[:12]}.so")
    if not os.path.exists(so_path):
        cmd = ["g++", "-O2", "-fPIC", "-shared", "-std=c++17",
               *(extra_cflags or []), *sources, "-o", so_path]
        if verbose:
            print("cpp_extension:", " ".join(cmd))
        proc = subprocess.run(cmd, capture_output=True, text=True)
        if proc.returncode != 0:
            raise RuntimeError(
                f"g++ failed (exit {proc.returncode}) for {name}:\n"
                f"{proc.stderr}")
        if verbose and proc.stderr:
            print(proc.stderr)
    return so_path


class _Extension:
    """Module-like handle over the compiled library: each declared op is a
    framework-op callable (Tensor in/out, jit-safe)."""

    def __init__(self, lib_path, functions):
        self._lib_path = lib_path
        self._lib = ctypes.CDLL(lib_path)
        for fname, n_inputs in functions.items():
            setattr(self, fname, self._make_op(fname, n_inputs))

    def _sym(self, fname, n_bufs):
        sym = getattr(self._lib, fname)
        sym.restype = None
        sym.argtypes = [ctypes.POINTER(ctypes.c_float)] * n_bufs \
            + [ctypes.c_int64]
        return sym

    def _host_call(self, sym):
        def host_fn(*arrays):
            ins = [np.ascontiguousarray(a, np.float32) for a in arrays]
            out = np.empty_like(ins[0])
            ptrs = [a.ctypes.data_as(ctypes.POINTER(ctypes.c_float))
                    for a in ins]
            sym(*ptrs, out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
                ctypes.c_int64(ins[0].size))
            return out
        return host_fn

    def _make_op(self, fname, n_inputs):
        import jax
        import jax.numpy as jnp

        from ..core.dispatch import apply_op
        from ..core.tensor import Tensor

        fwd_host = self._host_call(self._sym(fname, n_inputs + 1))
        try:
            bwd_host = self._host_call(self._sym(fname + "_grad",
                                                 n_inputs + 2))
        except AttributeError:
            bwd_host = None

        def fwd_raw(*arrs):
            if not any(isinstance(a, jax.core.Tracer) for a in arrs):
                # eager: call the C++ symbol directly on host (eager
                # needs no callback)
                return jnp.asarray(fwd_host(*[np.asarray(a)
                                              for a in arrs]))
            spec = jax.ShapeDtypeStruct(arrs[0].shape, jnp.float32)
            return jax.pure_callback(fwd_host, spec, *arrs,
                                     vmap_method="sequential")

        if bwd_host is None:
            def op(*tensors):
                ts = tuple(t if isinstance(t, Tensor)
                           else Tensor(jnp.asarray(t)) for t in tensors)
                return apply_op(f"custom_{fname}", fwd_raw, ts, {},
                                differentiable=False)
            op.__name__ = fname
            return op

        import functools

        @functools.partial(jax.custom_vjp)
        def fwd_diff(*arrs):
            return fwd_raw(*arrs)

        def _vjp_fwd(*arrs):
            return fwd_raw(*arrs), arrs

        def _vjp_bwd(res, g):
            # ABI: name_grad(in0[, in1...], cot, out, n) -> d/d_in0 only
            # (multi-input customs return the same-shaped grad for input 0
            # and zeros for the rest, like reference single-grad customs)
            if not any(isinstance(a, jax.core.Tracer) for a in (*res, g)):
                din0 = jnp.asarray(bwd_host(*[np.asarray(a) for a in res],
                                            np.asarray(g)))
            else:
                spec = jax.ShapeDtypeStruct(res[0].shape, jnp.float32)
                din0 = jax.pure_callback(bwd_host, spec, *res, g,
                                         vmap_method="sequential")
            return (din0,) + tuple(jnp.zeros_like(a) for a in res[1:])

        fwd_diff.defvjp(_vjp_fwd, _vjp_bwd)

        def op(*tensors):
            ts = tuple(t if isinstance(t, Tensor)
                       else Tensor(jnp.asarray(t)) for t in tensors)
            return apply_op(f"custom_{fname}", fwd_diff, ts, {})
        op.__name__ = fname
        return op


def load(name, sources, functions=None, extra_cflags=None,
         extra_cuda_cflags=None, extra_ldflags=None,
         extra_include_paths=None, build_directory=None, verbose=False):
    """reference cpp_extension.load:797 — JIT-compile and import.

    ``functions`` maps exported symbol → number of tensor inputs; if
    omitted, every ``extern "C"`` symbol must be declared via a
    ``// PD_OP: name n_inputs`` comment line in the source."""
    sources = [sources] if isinstance(sources, str) else list(sources)
    if functions is None:
        functions = {}
        for src in sources:
            with open(src) as f:
                for line in f:
                    if line.strip().startswith("// PD_OP:"):
                        parts = line.strip().split()
                        functions[parts[2]] = int(parts[3])
        if not functions:
            raise ValueError(
                "declare ops via functions={name: n_inputs} or "
                "'// PD_OP: name n_inputs' comments in the source")
    if extra_include_paths:
        extra_cflags = list(extra_cflags or []) + [
            f"-I{p}" for p in extra_include_paths]
    so_path = _compile(name, sources, extra_cflags, build_directory,
                       verbose)
    return _Extension(so_path, functions)


class CppExtension:
    """reference cpp_extension.CppExtension — declarative form consumed by
    :func:`setup`."""

    def __init__(self, sources, functions=None, **kwargs):
        self.sources = [sources] if isinstance(sources, str) else sources
        self.functions = functions
        self.kwargs = kwargs


def setup(name, ext_modules, **kwargs):
    """reference cpp_extension.setup:79 — eager build (no wheel machinery;
    returns the loaded extension)."""
    ext = ext_modules if isinstance(ext_modules, CppExtension) \
        else ext_modules[0]
    return load(name, ext.sources, ext.functions,
                extra_cflags=ext.kwargs.get("extra_compile_args"))
