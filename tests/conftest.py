import os
import sys

# the package is used from a checkout, not an install: make the suite
# runnable from any cwd by putting the repo root on sys.path
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Tests run on the CPU only: TPU/sharding tests use a virtual 8-device CPU
# mesh (the chip is reached through `python chip_smoke.py`). Must be
# configured before any jax import; the environment may pre-set
# JAX_PLATFORMS to a real accelerator, so override rather than setdefault.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Persistent XLA compilation cache: the TPU-engine tests compile dozens of
# large programs (~18s each cold); caching cuts repeat suite runs by
# several minutes. /tmp is machine-local, so a container migration can't
# replay AOT code compiled for a different CPU. The cache loader logs
# spurious ERROR lines about "prefer-no-scatter" pseudo-features differing
# from the detected host (a cosmetic XLA:CPU logging bug on same-machine
# reloads), so silence XLA's C++ log stream for test runs — test failures
# surface as Python exceptions, never via that stream.
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")

# Per-uid (like the uds socket dir): a shared path would leave second users
# unable to write AND trusting artifacts they don't own. Set through the
# environment, so entry points driven in-process by tests
# (madsim_tpu.compile_cache) leave it where it is, outside the checkout.
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR", f"/tmp/madsim_tpu_jaxcache-{os.getuid()}"
)

try:
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 2.0)
except ImportError:
    pass
