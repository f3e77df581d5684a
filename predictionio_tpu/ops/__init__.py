"""TPU compute kernels: ALS solvers, top-k retrieval, cooccurrence counting.

These replace the reference's use of Spark MLlib (``ALS.train`` /
``trainImplicit`` in the recommendation templates, cosine similarity in
similar-product, NaiveBayes in classification) with XLA-compiled JAX on
sharded arrays.
"""

from predictionio_tpu.ops.als import ALSConfig, als_train
from predictionio_tpu.ops.topk import ServingIndex

__all__ = ["ALSConfig", "ServingIndex", "als_train"]
