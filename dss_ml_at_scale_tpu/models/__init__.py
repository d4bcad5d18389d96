"""Model zoo (Flax) + metrics. Flagship: ResNet-50 image classifier."""

from .resnet import ResNet, ResNet18, ResNet50, ResNet101  # noqa: F401
from .vit import ViT, vit_t16, vit_s16  # noqa: F401
from .metrics import (  # noqa: F401
    cross_entropy_loss,
    multiclass_accuracy,
    topk_accuracy,
)
from .transformer import (  # noqa: F401
    RMSNorm,
    TransformerLM,
    generate,
    init_kv_cache,
    next_token_loss,
)
from .moe import MoEMLP, collect_aux_loss  # noqa: F401
from .mla_moe import MlaMoeLM  # noqa: F401
from .pipelined_lm import PipelinedLM, PipelinedLMTask  # noqa: F401
