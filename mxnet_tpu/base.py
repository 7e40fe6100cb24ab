"""Core shared machinery: errors, dtype mapping, parameter reflection, registries.

TPU-native re-implementation of the roles played in the reference by dmlc-core:
- error type (`dmlc::Error` -> MXNetError)
- `dmlc::Parameter` reflection structs (reference: DMLC_REGISTER_PARAMETER, 132 uses,
  e.g. src/operator/nn/fully_connected.cc) -> :class:`Params`
- env-var config (reference: docs/faq/env_var.md) -> :func:`get_env`
"""
from __future__ import annotations

import os
import numpy as _np

string_types = (str,)
numeric_types = (float, int, _np.generic)
integer_types = (int, _np.integer)

__all__ = [
    "MXNetError", "NotSupportedForSparseNDArray", "Params", "param_field",
    "get_env", "env_flag", "configure_compile_cache", "compile_cache_dir",
    "string_types", "numeric_types", "integer_types",
]


class MXNetError(Exception):
    """Error raised by the framework (reference: dmlc::Error surfaced via MXGetLastError)."""


class NotSupportedForSparseNDArray(MXNetError):
    def __init__(self, function, alias, *args):
        msg = "Function {}".format(function.__name__ if hasattr(function, "__name__") else function)
        if alias:
            msg += " (alias {})".format(alias)
        if args:
            msg += " with arguments ({})".format(", ".join(str(a) for a in args))
        msg += " is not supported for SparseNDArray."
        super().__init__(msg)


# ---------------------------------------------------------------------------
# dtype mapping (reference: include/mxnet/base.h mshadow type enum)
# ---------------------------------------------------------------------------

_DTYPE_NP_TO_MX = {
    None: -1,
    _np.float32: 0,
    _np.float64: 1,
    _np.float16: 2,
    _np.uint8: 3,
    _np.int32: 4,
    _np.int8: 5,
    _np.int64: 6,
}
_DTYPE_MX_TO_NP = {v: k for k, v in _DTYPE_NP_TO_MX.items()}

try:  # bfloat16 is TPU-native; expose it as a first-class dtype
    import ml_dtypes as _ml_dtypes
    bfloat16 = _np.dtype(_ml_dtypes.bfloat16)
    _DTYPE_NP_TO_MX[bfloat16.type] = 12
    _DTYPE_MX_TO_NP[12] = bfloat16.type
except ImportError:  # pragma: no cover
    bfloat16 = None


def np_dtype(dtype):
    """Normalise a user dtype spec (str/np.dtype/type) to a numpy dtype object."""
    if dtype is None:
        return _np.dtype(_np.float32)
    if isinstance(dtype, str) and dtype == "bfloat16" and bfloat16 is not None:
        return bfloat16
    return _np.dtype(dtype)


# ---------------------------------------------------------------------------
# env config (reference: dmlc::GetEnv at point of use; docs/faq/env_var.md)
# ---------------------------------------------------------------------------

def get_env(name, default=None, typ=str):
    val = os.environ.get(name)
    if val is None:
        return default
    try:
        if typ is bool:
            return val not in ("0", "false", "False", "")
        return typ(val)
    except ValueError:
        return default


def env_flag(name, default=False):
    return get_env(name, default, bool)


_compile_cache_state = {"configured": False, "dir": None}


def configure_compile_cache():
    """Place JAX's persistent compilation cache (docs/faq/env_var.md).

    Where `JAX_COMPILATION_CACHE_DIR` (or an earlier `jax.config` update)
    already names a directory, that directory is the cache and this sets no
    other: whoever runs the program places its cache from outside. Otherwise
    `MXNET_TPU_COMPILE_CACHE`, when it names a directory, is wired in, so
    XLA executables — including every serving bucket program — survive
    process restarts and a redeployed engine's re-warmup is a disk read.

    Idempotent and safe to call from any number of entry points (serving
    program cache, Executor.warmup). Returns the active cache dir or None."""
    if _compile_cache_state["configured"]:
        return _compile_cache_state["dir"]
    _compile_cache_state["configured"] = True
    import jax
    current = jax.config.jax_compilation_cache_dir
    if current:
        _compile_cache_state["dir"] = current
        return current
    path = get_env("MXNET_TPU_COMPILE_CACHE")
    if not path:
        return None
    jax.config.update("jax_compilation_cache_dir", path)
    # serving bucket programs are small and fast-compiling relative to
    # train steps; cache them all so warmup hits disk, not XLA
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # jax initializes its compilation cache LAZILY on the first compile and
    # then never re-reads the config; a compile before this call froze it
    # as "disabled". Reset it so the next compile re-initializes against
    # the dir above.
    from jax.experimental.compilation_cache import compilation_cache as _cc
    _cc.reset_cache()
    _compile_cache_state["dir"] = path
    return path


def compile_cache_dir():
    """The persistent compile-cache directory in effect, or None. Pure
    state read (no env access) — safe on dispatch-adjacent paths like
    ``profiler.compile_counters``."""
    return _compile_cache_state["dir"]


def atomic_write(fname, data, mode="wb"):
    """Write `data` to `fname` via a unique temp file + os.replace.

    Checkpoint writers can run on background threads that die with the
    process, and several writers may target the same path concurrently
    (epoch-N background save still in flight when epoch N+1 starts) — a
    per-call mkstemp temp plus an atomic rename means the file at `fname`
    is always a complete, self-consistent write, never truncated or
    interleaved.

    Semantics differ from plain open(fname): the PARENT DIRECTORY must be
    writable (the temp lives beside the target), and a symlink at `fname`
    is replaced by a regular file rather than written through. The mode
    of an existing target is preserved; new files get umask-default."""
    import tempfile
    d = os.path.dirname(os.path.abspath(fname))
    fd, tmp = tempfile.mkstemp(dir=d,
                               prefix=os.path.basename(fname) + ".tmp-")
    try:
        with os.fdopen(fd, mode) as f:
            f.write(data)
        # mkstemp creates 0600; restore what a plain open() would have
        # produced (umask-masked 0666, or the target's existing mode) so
        # the atomicity refactor doesn't regress file shareability
        try:
            mode_bits = os.stat(fname).st_mode & 0o7777
        except OSError:
            umask = os.umask(0)
            os.umask(umask)
            mode_bits = 0o666 & ~umask
        os.chmod(tmp, mode_bits)
        os.replace(tmp, fname)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


# ---------------------------------------------------------------------------
# Parameter reflection (reference: dmlc::Parameter / DMLC_REGISTER_PARAMETER).
# Gives every op/iterator auto-documented, string-coercible kwargs — powers the
# symbol JSON round-trip where all attrs are strings.
# ---------------------------------------------------------------------------

class _Field:
    __slots__ = ("name", "type", "default", "required", "doc", "enum")

    def __init__(self, type=str, default=None, required=False, doc="", enum=None):
        self.name = None
        self.type = type
        self.default = default
        self.required = required
        self.doc = doc
        self.enum = enum


def param_field(type=str, default=None, required=False, doc="", enum=None):
    return _Field(type=type, default=default, required=required, doc=doc, enum=enum)


def _is_jax_tracer(x):
    try:
        import jax
        return isinstance(x, jax.core.Tracer)
    except Exception:  # pragma: no cover - jax always present in practice
        return False


def _coerce(value, typ):
    """Coerce a (possibly string-serialized) value to the declared field type."""
    if value is None:
        return None
    if typ is bool:
        if isinstance(value, str):
            return value.lower() in ("1", "true", "yes")
        return bool(value)
    if typ in (int, float):
        try:
            return typ(value)
        except TypeError:
            # jax tracers can't concretize to python scalars; inside a
            # traced region (e.g. the fused Trainer update, where lr is a
            # runtime argument) pass them through — all downstream use is
            # jnp arithmetic
            if _is_jax_tracer(value):
                return value
            raise
    if typ is tuple:  # shape-like "(1, 2)" / float-list "(1, 0.5)" strings
        def elem(x):
            f = float(x)
            return int(f) if f.is_integer() else f
        if isinstance(value, str):
            s = value.strip().strip("()[]")
            if not s:
                return ()
            return tuple(elem(x) for x in s.replace(" ", "").split(",") if x != "")
        if isinstance(value, (list, tuple)):
            return tuple(elem(v) for v in value)
        return (elem(value),)
    if typ is str:
        return str(value)
    return typ(value)


class ParamsMeta(type):
    def __new__(mcs, name, bases, ns):
        fields = {}
        for base in bases:
            fields.update(getattr(base, "_fields", {}))
        for key, val in list(ns.items()):
            if isinstance(val, _Field):
                val.name = key
                fields[key] = val
                del ns[key]
        ns["_fields"] = fields
        return super().__new__(mcs, name, bases, ns)


class Params(metaclass=ParamsMeta):
    """Typed, string-coercible parameter struct.

    Subclass with `param_field` class attributes; instantiate with kwargs (values
    may be strings, as when reloading symbol JSON). Unknown kwargs raise.
    """

    def __init__(self, **kwargs):
        for fname, field in self._fields.items():
            if fname in kwargs:
                val = _coerce(kwargs.pop(fname), field.type)
                if field.enum is not None and val is not None and val not in field.enum:
                    raise MXNetError(
                        "Invalid value %r for parameter %s; expected one of %s"
                        % (val, fname, field.enum))
                setattr(self, fname, val)
            elif field.required:
                raise MXNetError("Required parameter %s missing" % fname)
            else:
                setattr(self, fname, field.default)
        if kwargs:
            raise MXNetError(
                "Unknown parameters %s for %s" % (sorted(kwargs), type(self).__name__))

    def as_dict(self):
        return {k: getattr(self, k) for k in self._fields}

    def as_str_dict(self):
        """Stringify for symbol JSON serialization (reference stores attrs as strings)."""
        out = {}
        for k in self._fields:
            v = getattr(self, k)
            if v is None:
                continue
            out[k] = str(v)
        return out

    def __repr__(self):
        return "%s(%s)" % (type(self).__name__,
                           ", ".join("%s=%r" % (k, getattr(self, k)) for k in self._fields))


# ---------------------------------------------------------------------------
# Generic registry (reference: python/mxnet/registry.py get_register_func)
# ---------------------------------------------------------------------------

class Registry:
    def __init__(self, kind):
        self.kind = kind
        self._map = {}

    def register(self, obj, name=None):
        name = (name or getattr(obj, "__name__", None) or str(obj)).lower()
        self._map[name] = obj
        return obj

    def alias(self, obj, *names):
        for n in names:
            self._map[n.lower()] = obj
        return obj

    def get(self, name):
        key = name.lower() if isinstance(name, str) else name
        if key not in self._map:
            raise MXNetError("%s %r is not registered. Registered: %s"
                             % (self.kind, name, sorted(self._map)))
        return self._map[key]

    def find(self, name):
        return self._map.get(name.lower() if isinstance(name, str) else name)

    def create(self, spec, **kwargs):
        """Create from name / (name, kwargs) / instance — mirrors registry.create."""
        if isinstance(spec, str):
            return self.get(spec)(**kwargs)
        return spec

    def keys(self):
        return sorted(self._map)
