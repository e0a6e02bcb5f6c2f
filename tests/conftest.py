"""Suite-wide environment: no persistent compilation cache.

The entry points turn JAX's persistent cache on
(:func:`repro.launch.env.enable_compile_cache`); tests that run them, in
this process or as subprocesses, must not write one into the checkout.
The variable covers subprocesses; the config update covers this process
even when a plugin imported jax before this file ran.
"""
import os

os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax  # noqa: E402

jax.config.update("jax_enable_compilation_cache", False)
