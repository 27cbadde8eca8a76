"""repro — EmbML-JAX: embedded-inference model conversion at pod scale.

Faithful JAX reproduction of *An Open-Source Tool for Classification Models in
Resource-Constrained Hardware* (EmbML, IEEE Sensors Journal 2021), extended
into a production multi-pod training/serving framework (see DESIGN.md).
"""

import jax

# Q22.10 (FXP32) fixed-point arithmetic requires 64-bit integer intermediates
# for products/accumulations — exactly as the paper's fixedptc/libfixmath base
# does on MCUs.  JAX truncates int64 to int32 unless x64 is enabled.  All
# higher layers (LM stack, kernels) pass explicit dtypes, so enabling x64 here
# does not change any model numerics.
jax.config.update("jax_enable_x64", True)
# Seeded weights (trained classifiers, LM init) come from the threefry
# bit-stream JAX used before 0.5 made "partitionable" the default; the
# golden vectors in tests/golden were computed from it.  Pinning it keeps
# every seed's weights the same on any installed JAX.
jax.config.update("jax_threefry_partitionable", False)

__version__ = "1.0.0"
