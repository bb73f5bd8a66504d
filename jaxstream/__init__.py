"""jaxstream — TPU-native cubed-sphere shallow-water framework.

Importing the package places jax's persistent compilation cache
(``jaxstream.utils.jax_compat.place_compile_cache``): where
``JAX_COMPILATION_CACHE_DIR`` is set jax uses that directory, otherwise
``<repo>/.jax_cache``.  Every entry point — ``Simulation``, the CLI,
``chip_smoke.py``, ``bench.py`` — shares it.
"""

from .utils.jax_compat import place_compile_cache

place_compile_cache()
