"""TPU-native model families.

The reference owns no models (they come from `transformers` and are rewritten
post-hoc); a TPU-native framework owns them because scan-over-layers structure,
sharding plans, and attention kernels are the performance story. Each family
module exposes: a frozen ``*Config``, ``init(rng, config) -> params``,
``forward``/``loss_fn`` pure functions, and a registered TP plan
(`parallel/tp.py`).
"""

from . import bert, gpt, hf, llama, olmo_hybrid, smallthinker, t5, vit
from .hf import from_hf_config, load_pretrained, save_pretrained
from .layers import cross_entropy_loss, dot_product_attention

__all__ = [
    "bert", "gpt", "hf", "llama", "olmo_hybrid", "smallthinker", "t5", "vit",
    "cross_entropy_loss", "dot_product_attention",
    "from_hf_config", "load_pretrained", "save_pretrained",
]
