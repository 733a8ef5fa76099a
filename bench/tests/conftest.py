"""The benchmark's own tests run on the CPU:

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m pytest bench/tests -q
"""

import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax  # noqa: E402

# Runs here compile for the CPU.  JAX's persistent cache (which the
# program's entry points turn on) would hand one process programs that
# another compiled for other CPU features; the tests compile afresh.
jax.config.update("jax_enable_compilation_cache", False)
# On the CPU, jnp.asarray of an aligned numpy array aliases its memory,
# and the engine updates host arrays in place after handing them to a
# dispatch (see PERF.md, Open questions); synchronous dispatch keeps the
# tests' CPU runs from racing with those updates.
jax.config.update("jax_cpu_enable_async_dispatch", False)
