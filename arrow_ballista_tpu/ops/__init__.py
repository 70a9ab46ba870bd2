"""TPU compute plane: fused relational kernels over JAX/XLA.

Dtype policy (``kernels.precision_mode``): the CPU platform runs f64/i64
kernels ("x64" — exact vs pyarrow oracles); TPU runs native f32/i32
("x32") with double-float compensated sums, since v5e has no f64/i64 ALUs.
``jax_enable_x64`` is enabled globally so the x64 mode can exist at all;
x32-mode kernels pin every dtype explicitly and never materialize a 64-bit
device array, so the flag is harmless on TPU.

Every process that runs kernels imports this package, so it is also where
the persistent XLA compile cache is placed: ``JAX_COMPILATION_CACHE_DIR``
wins when the environment sets it (jax reads it itself — nothing is set
here); otherwise the cache lives at the fixed ``<checkout>/.jax_cache``.
The path is part of the cache key, so it is never a temp name.
"""

import os

import jax

jax.config.update("jax_enable_x64", True)

if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update(
        "jax_compilation_cache_dir",
        os.path.join(
            os.path.dirname(
                os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
            ),
            ".jax_cache",
        ),
    )
