"""Test-session settings.

The CLIs turn on JAX's persistent compilation cache
(``repro.compile_cache``); tests, in process and in the subprocesses
they start, keep it off so a run never reads or writes cache entries.
"""
import os

os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax  # noqa: E402

jax.config.update("jax_enable_compilation_cache", False)
