"""CAMformer reproduction package.

Importing it does not import JAX, so an entry point can configure JAX
(``repro.utils.compile_cache``) before the first compile.
"""
