"""Native flax model zoo covering the reference's benchmark model families.

The reference era's models were Keras 1.x MLP/CNN/LSTM (SURVEY.md §5.7); the
five BASELINE configs map to:

- :func:`mlp` — MNIST 3-layer MLP (config 1) and ATLAS-Higgs tabular MLP
  (config 4);
- :func:`lenet` — MNIST LeNet-style CNN (config 2, the north-star config);
- :func:`vgg_small` — CIFAR-10 VGG-small (config 3);
- :func:`lstm_classifier` — IMDB LSTM sentiment (config 5);
- :func:`transformer_classifier` — beyond-reference long-context family whose
  attention math is shared with ``parallel.ring_attention`` (sequence
  parallelism);
- :func:`resnet_small` — beyond-reference batch-norm family: BatchNorm
  running stats ride the engines' non-trainable-state path (per-worker
  stats, the standard data-parallel BN);
- :func:`transformer_lm` — beyond-reference decoder-only causal LM with
  KV-cached autoregressive :func:`~distkeras_tpu.models.lm.generate`
  (prefill + one ``lax.scan`` decode loop, static shapes throughout).

All models emit **logits** (pair with the ``softmax_cross_entropy`` family) and
default to bfloat16 activations with float32 parameters — bf16 keeps matmuls
and convs on the MXU's fast path while fp32 master weights keep optimizer math
exact.
"""

from distkeras_tpu.models.mlp import MLP, mlp
from distkeras_tpu.models.cnn import LeNet, VGGSmall, lenet, vgg_small
from distkeras_tpu.models.lstm import LSTMClassifier, lstm_classifier
from distkeras_tpu.models.moe import (
    MoETransformerClassifier,
    moe_transformer_classifier,
)
from distkeras_tpu.models.lm import (
    MlaDims,
    SdarDims,
    TransformerLM,
    ZayaDims,
    beam_search,
    generate,
    speculative_generate,
    next_token_dataset,
    quantize_lm,
    transformer_lm,
)
from distkeras_tpu.models.resnet import ResNetSmall, resnet_small
from distkeras_tpu.models.sru import SRUClassifier, sru_classifier
from distkeras_tpu.models.transformer import (
    TransformerClassifier,
    pipelined_transformer_forward,
    sequence_parallel_transformer_forward,
    transformer_classifier,
)

__all__ = [
    "MLP", "mlp",
    "LeNet", "lenet",
    "VGGSmall", "vgg_small",
    "LSTMClassifier", "lstm_classifier",
    "SRUClassifier", "sru_classifier",
    "ResNetSmall", "resnet_small",
    "TransformerClassifier", "transformer_classifier",
    "pipelined_transformer_forward",
    "sequence_parallel_transformer_forward",
    "MoETransformerClassifier", "moe_transformer_classifier",
    "TransformerLM", "MlaDims", "SdarDims", "ZayaDims", "transformer_lm", "generate", "beam_search",
    "speculative_generate",
    "next_token_dataset", "quantize_lm",
]
