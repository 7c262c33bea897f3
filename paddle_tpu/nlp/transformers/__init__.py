from .ernie import (  # noqa: F401
    BertConfig, BertForPretraining, BertModel, BertPretrainingCriterion,
    ErnieConfig, ErnieForPretraining, ErnieForSequenceClassification,
    ErnieModel, ErniePretrainingCriterion, bert_config, ernie_config,
)
from .gpt import (  # noqa: F401
    GPTConfig, GPTDecoderLayer, GPTForPretraining, GPTModel,
    GPTPretrainingCriterion, gpt_config,
)
from .latent_moe import (  # noqa: F401
    HeldExperts, LatentAttention, LatentMoEConfig, LatentMoEDecoderLayer,
    LatentMoEForCausalLM, LatentMoEModel,
)
from .hybrid_linear import (  # noqa: F401
    GatedDeltaNet, HybridDecoderLayer, HybridFullAttention,
    HybridLinearConfig, HybridLinearForCausalLM, HybridLinearModel,
)
from .window_moe import (  # noqa: F401
    WindowAttention, WindowMoEConfig, WindowMoEDecoderLayer,
    WindowMoEForCausalLM, WindowMoEModel,
)
