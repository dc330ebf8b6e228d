"""Test processes, and the subprocesses they start, keep no persistent
compilation cache: entry points called from tests (``launch/train.py``'s
``main``) would otherwise place one in the checkout, and the TPU
compile-only tests cannot read back what they write."""
import os

os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax  # noqa: E402

jax.config.update("jax_enable_compilation_cache", False)
