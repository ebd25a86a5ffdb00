"""JAX compute kernels (the rebuild's "native layer").

The reference's kernel layer is Spark MLlib invoked from engine templates
(SURVEY.md intro); here it is hand-written JAX designed for the TPU:
segment-sum Gramians feeding the MXU-batched Cholesky solves of ALS,
vectorized counting for NaiveBayes, optax-driven LogReg, and sparse
cooccurrence counting. `attention` adds the long-context layer: flash-style
blockwise attention plus ring sequence parallelism over a Mesh.
"""

from predictionio_tpu.ops.attention import (   # noqa: F401
    blockwise_attention, mha, ring_attention,
)
