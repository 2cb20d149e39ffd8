"""Model zoo: the architectures the reference benchmarks with (ResNet family,
MNIST MLP) plus the rebuild's BERT target (BASELINE.md)."""

from .bert import (  # noqa: F401
    BERT_BASE,
    BERT_LARGE,
    BERT_TINY,
    BertConfig,
    BertEncoder,
    mlm_loss,
)
from .llama import (  # noqa: F401
    LLAMA_1B,
    LLAMA_300M,
    LLAMA_8B,
    LLAMA_TINY,
    DecodePath,
    LlamaConfig,
    LlamaLM,
    classify_decode_sharding,
    generate,
    init_kv_cache,
    llama_tp_param_specs,
)
from .losses import (  # noqa: F401
    causal_lm_loss,
    chunked_causal_lm_loss,
    sp_causal_lm_loss,
    token_nll,
    weighted_chunked_causal_lm_loss,
)
from .inception import InceptionV3  # noqa: F401
from .joyai import (  # noqa: F401
    JOYAI_LLM_FLASH,
    JOYAI_TINY,
    JoyAIConfig,
    JoyAILM,
    joyai_lm_loss,
)
from .laguna import (  # noqa: F401
    LAGUNA_TINY,
    LAGUNA_XS2,
    LagunaConfig,
    LagunaLM,
)
from .lfm2 import (  # noqa: F401
    LFM2_24B_A2B,
    LFM2_TINY,
    Lfm2Config,
    Lfm2LM,
)
from .moe_lm import (  # noqa: F401
    MOE_SMALL,
    MOE_TINY,
    MoeConfig,
    MoeLM,
)
from .mlp import MnistMLP  # noqa: F401
from .olmo_hybrid import (  # noqa: F401
    OLMO_HYBRID_TINY,
    OlmoHybridConfig,
    OlmoHybridLM,
)
from .ouro import (  # noqa: F401
    OURO_2_6B,
    OURO_TINY,
    OuroConfig,
    OuroLM,
    exit_distribution,
    ouro_lm_loss,
)
from .smallthinker import (  # noqa: F401
    SMALLTHINKER_21B,
    SMALLTHINKER_TINY,
    SmallThinkerConfig,
    SmallThinkerLM,
)
from .resnet import (  # noqa: F401
    ResNet,
    ResNet50,
    ResNet101,
    ResNet152,
    ResNetTiny,
)
from .vgg import (  # noqa: F401
    VGG,
    VGG11,
    VGG13,
    VGG16,
    VGG19,
    VGGTiny,
)
from .vit import (  # noqa: F401
    VIT_B16,
    VIT_S16,
    VIT_TINY,
    VisionTransformer,
    ViTConfig,
    classification_loss,
)
