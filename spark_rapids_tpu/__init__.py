"""spark-rapids-tpu: a TPU-native columnar SQL acceleration framework.

A from-scratch re-design of the capabilities of the RAPIDS Accelerator for
Apache Spark (reference: tgravescs/spark-rapids) targeting TPUs through
JAX/XLA instead of NVIDIA GPUs through cuDF/RMM/UCX.

Architecture (bottom-up), mirroring the reference's layer map (SURVEY.md section 1):

  L0  jax/XLA kernels                   (reference: external cuDF/RMM/UCX)
  L2  memory & device runtime           (reference: GpuDeviceManager/GpuSemaphore/
                                         RapidsBufferCatalog + spill stores)
  L3  I/O + exchange                    (reference: GpuParquetScan, shuffle)
  L4  columnar operators & expressions  (reference: Gpu*Exec / Gpu* expressions)
  L5  plan-rewrite engine               (reference: GpuOverrides + RapidsMeta +
                                         GpuTransitionOverrides)
  L6/L7 session front-end & conf        (reference: Plugin.scala / RapidsConf.scala)

The reference is a plugin into Apache Spark; this framework carries its own
Spark-like front-end (session/DataFrame/logical plan) because it is standalone,
but the heart of the design is the same: a CPU physical plan is *tagged*
node-by-node for TPU support (with human-readable reasons) and *converted* into
TPU columnar operators, with explicit host<->device transition operators and
CPU fallback for anything unsupported.

64-bit note: SQL semantics require int64/float64; we enable jax x64 at import.
TPU executes s64/f64 via XLA emulation; hot paths can opt into 32-bit via conf.
"""

import os as _os

import jax as _jax

_jax.config.update("jax_enable_x64", True)

# Persistent XLA executable cache: the per-process kernel cache
# (utils/kernelcache.py) cannot carry compiles across runs. The directory
# is part of where a later process looks, so it is either the one the
# environment names or one fixed path — never a per-run path.
_REPO_CACHE_DIR = _os.path.join(
    _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
    ".jax_cache")


def configure_compile_cache():
    """Decide the persistent compile cache once the backend is resolved
    (memory/device.py calls this; env pinning alone misses the
    no-accelerator-present case). Returns the directory in force, or
    None when the cache stays off.

    Where JAX_COMPILATION_CACHE_DIR is set jax reads it itself and no
    code sets another directory. Otherwise an accelerator backend caches
    under ``<checkout>/.jax_cache`` and XLA:CPU keeps the cache off (its
    AOT reload warns about machine-feature mismatches — prefer-no-scatter
    et al. — with SIGILL risk). Every executable is stored whatever its
    size or compile time: a sweep's warm-up is a thousand small programs,
    not a few slow ones. A directory that cannot be created raises."""
    cache_dir = _os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        if _jax.default_backend() == "cpu":
            return None
        cache_dir = _REPO_CACHE_DIR
        _os.makedirs(cache_dir, exist_ok=True)
        _jax.config.update("jax_compilation_cache_dir", cache_dir)
    _jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    _jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir


__version__ = "0.1.0"

from spark_rapids_tpu.config.conf import TpuConf, conf_entries  # noqa: E402,F401
