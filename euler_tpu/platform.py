"""jax backend bootstrap shared by every process entry point (bench.py,
chip_smoke.py, examples, tools).

One function, one process. A chip belongs to one process at a time, so
the backend is initialized HERE, in the calling process, exactly once —
no child process queries devices first, and a backend that fails to
initialize raises instead of retargeting to another platform. (Reference
analog: euler initializes its engine explicitly at process start,
euler/client/query_proxy.cc:39; here the accelerator backend is the
resource with explicit init.)

The same function places the persistent compilation cache: the
directory named by ``JAX_COMPILATION_CACHE_DIR`` when the environment
sets it (jax reads that itself), otherwise one fixed path inside the
checkout (``<checkout>/.jax_cache``, git-ignored). The path is part of
the cache key, so it is never derived from a temp name, pid or time.
"""

from __future__ import annotations

import os
import sys

_state = {"initialized": None}

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_COMPILE_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def add_platform_flag(parser, default: str = "auto"):
    """Attach the shared --platform flag to an argparse parser."""
    parser.add_argument(
        "--platform", default=default, choices=["auto", "tpu", "cpu"],
        help="accelerator backend: auto = whatever backend jax selects "
             "in this process; tpu = require the TPU (raise otherwise); "
             "cpu = force CPU")
    return parser


def compile_cache_dir() -> str:
    """The persistent compilation cache directory in use."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or DEFAULT_COMPILE_CACHE_DIR


def init_platform(platform: str = "auto", n_devices=None) -> str:
    """Initialize the jax backend in this process; returns its name.

    platform:
      cpu  — force the CPU backend (with n_devices virtual devices for
             sharding runs) before the first device query.
      tpu  — initialize jax here with the explicit platform list
             "tpu,cpu" (an explicit list makes jax itself fail loudly
             when the TPU will not initialize; the CPU backend rides
             along second for host-side references, jax.devices("cpu"))
             and raise unless the first device is a TPU. Never returns
             another backend.
      auto — initialize whatever backend jax itself selects.

    Prints platform / device_kind / count / cache dir on stderr.
    Idempotent: repeat calls return the already-chosen backend.
    """
    import jax

    if _state["initialized"]:
        return _state["initialized"]

    env_pick = os.environ.get("EULER_TPU_PLATFORM", "").strip().lower()
    if platform == "auto" and env_pick in ("cpu", "tpu"):
        platform = env_pick

    # the ONE place this code base sets a compile cache directory (jax
    # reads JAX_COMPILATION_CACHE_DIR itself when the environment sets it)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          DEFAULT_COMPILE_CACHE_DIR)
    if platform == "cpu":
        jax.config.update("jax_platforms", "cpu")
        if n_devices:
            jax.config.update("jax_num_cpu_devices", int(n_devices))
    elif platform == "tpu":
        jax.config.update("jax_platforms", "tpu,cpu")
    dev = jax.devices()[0]  # the in-process backend init
    if platform == "tpu" and dev.platform != "tpu":
        raise RuntimeError(
            f"--platform tpu requested but jax initialized "
            f"{dev.platform!r} ({dev.device_kind}); no TPU is attached "
            "to this process")
    print(f"[euler_tpu.platform] platform={dev.platform} "
          f"device_kind={dev.device_kind} count={jax.device_count()} "
          f"compile_cache={compile_cache_dir()}", file=sys.stderr)
    _state["initialized"] = dev.platform
    return dev.platform
