"""Process-wide environment singleton.

TPU-native analog of libnd4j's ``sd::Environment`` + ND4J's
``Nd4j.getEnvironment()`` (reference: libnd4j/include/system/Environment.h,
nd4j-api org/nd4j/linalg/factory/Environment.java). Fronts jax.config knobs,
XLA flags, and framework toggles behind one object so user code has a single
place to flip debug/verbose/determinism, matching the reference's pattern of
env-var + runtime-settable flags.
"""

from __future__ import annotations

import os
import threading
from typing import Any, Dict, List, Optional


class Environment:
    _instance: Optional["Environment"] = None
    _lock = threading.Lock()

    def __init__(self) -> None:
        self._debug = _env_bool("DL4J_TPU_DEBUG", False)
        self._verbose = _env_bool("DL4J_TPU_VERBOSE", False)
        self._profiling = False
        self._check_nan = False          # NAN_PANIC analog (jax_debug_nans)
        self._deterministic = _env_bool("DL4J_TPU_DETERMINISTIC", False)
        self._default_dtype = os.environ.get("DL4J_TPU_DTYPE", "float32")
        self._allow_pallas = _env_bool("DL4J_TPU_ALLOW_PALLAS", True)
        self._properties: Dict[str, Any] = {}

    # ------------------------------------------------------------------
    @classmethod
    def get(cls) -> "Environment":
        with cls._lock:
            if cls._instance is None:
                cls._instance = cls()
            return cls._instance

    # --- flags ---------------------------------------------------------
    def is_debug(self) -> bool:
        return self._debug

    def set_debug(self, v: bool) -> None:
        self._debug = bool(v)

    def is_verbose(self) -> bool:
        return self._verbose

    def set_verbose(self, v: bool) -> None:
        self._verbose = bool(v)

    def is_profiling(self) -> bool:
        return self._profiling

    def set_profiling(self, v: bool) -> None:
        self._profiling = bool(v)

    def is_check_nan(self) -> bool:
        return self._check_nan

    def set_check_nan(self, v: bool) -> None:
        """NAN_PANIC analog: makes jax raise on any NaN produced under jit."""
        import jax

        self._check_nan = bool(v)
        jax.config.update("jax_debug_nans", bool(v))

    def is_deterministic(self) -> bool:
        return self._deterministic

    def set_deterministic(self, v: bool) -> None:
        self._deterministic = bool(v)

    def allow_pallas(self) -> bool:
        return self._allow_pallas

    def set_allow_pallas(self, v: bool) -> None:
        self._allow_pallas = bool(v)

    def default_dtype(self) -> str:
        return self._default_dtype

    def set_default_dtype(self, name: str) -> None:
        self._default_dtype = name

    def compile_cache_dir(self) -> Optional[str]:
        """The persistent executable cache directory JAX is using, or None
        when the cache is off."""
        import jax

        return jax.config.jax_compilation_cache_dir or None

    def set_compile_cache(self, path: Optional[str] = None,
                          min_compile_secs: float = 1.0) -> str:
        """Enable the persistent executable cache (see
        :func:`enable_compilation_cache`); an explicit ``path`` is the
        caller's own choice and is used as given."""
        return enable_compilation_cache(path, min_compile_secs)

    # --- device info -----------------------------------------------------
    def devices(self) -> List[Any]:
        import jax

        return jax.devices()

    def num_devices(self) -> int:
        return len(self.devices())

    def is_tpu(self) -> bool:
        return any(d.platform == "tpu" for d in self.devices())

    # --- generic key/value (ND4JSystemProperties analog) -----------------
    def set_property(self, key: str, value: Any) -> None:
        self._properties[key] = value

    def get_property(self, key: str, default: Any = None) -> Any:
        return self._properties.get(key, default)


def _env_bool(name: str, default: bool) -> bool:
    v = os.environ.get(name)
    if v is None:
        return default
    return v.strip().lower() in ("1", "true", "yes", "on")


#: ``<checkout>/.jax_cache`` — derived from this file, so it is the same
#: directory whatever the working directory (the path is part of JAX's
#: cache key: a cache that moves never hits).
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compilation_cache(path: Optional[str] = None,
                             min_compile_secs: float = 1.0) -> str:
    """Turn on JAX's persistent executable cache (the TPU analog of the
    reference shipping pre-built libnd4j kernels: compile once per machine,
    not once per process). Returns the directory in use.

    The one placement rule: where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
    reads it itself and no directory is set in code; otherwise the cache is
    :data:`DEFAULT_COMPILE_CACHE_DIR`. An explicit ``path``
    (``Environment.set_compile_cache(path)``) is used as given.
    """
    import jax

    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path is None and env_dir:
        # jax read the variable when it was imported
        if jax.config.jax_compilation_cache_dir != env_dir:
            raise RuntimeError(
                f"JAX_COMPILATION_CACHE_DIR={env_dir!r} but jax's cache "
                f"directory is {jax.config.jax_compilation_cache_dir!r}: "
                "the variable was set after jax was imported, or code set "
                "another directory")
        path = env_dir
    else:
        path = path or DEFAULT_COMPILE_CACHE_DIR
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      min_compile_secs)
    return path
