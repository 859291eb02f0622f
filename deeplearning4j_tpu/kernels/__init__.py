"""Pallas TPU kernels registered behind the nn.helpers seam (the analog of
the reference's deeplearning4j-cuda module: cuDNN implementations discovered
behind the Helper SPI, SURVEY.md §2.2). Import and call ``register_*`` to
install — the moral equivalent of putting the cuda jar on the classpath."""

from .lstm import lstm_helper, register_lstm_helper
from .batchnorm import bn_train_fused, register_default as register_bn_helper

__all__ = ["lstm_helper", "register_lstm_helper",
           "bn_train_fused", "register_bn_helper"]
