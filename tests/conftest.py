"""Session set-up shared by every test module.

The entry points' ``main()`` turn on JAX's persistent compile cache
(``repro.launch.compile_cache``), and several tests call them in-process
or in a subprocess. The cache is off for the whole test session: no
compiled program is written into the checkout or read back from an
earlier run, so each run compiles what it checks.
"""
import os

os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"  # subprocesses too

import jax  # noqa: E402

jax.config.update("jax_enable_compilation_cache", False)
