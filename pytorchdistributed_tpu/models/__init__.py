from pytorchdistributed_tpu.models.mlp import MLP, LinearRegression  # noqa: F401
from pytorchdistributed_tpu.models.transformer import (  # noqa: F401
    TransformerConfig,
    TransformerBlock,
    TransformerStack,
)
from pytorchdistributed_tpu.models.gpt2 import GPT2, gpt2_config  # noqa: F401
from pytorchdistributed_tpu.models.llama import Llama, llama_config  # noqa: F401
from pytorchdistributed_tpu.models.moe import DroplessMoE, SwitchMoE  # noqa: F401
from pytorchdistributed_tpu.models.latent import (  # noqa: F401
    LatentConfig,
    LatentDims,
    LatentLM,
)
from pytorchdistributed_tpu.models.bert import BertMLM, bert_config  # noqa: F401
from pytorchdistributed_tpu.models.vit import ViT, ViTConfig, vit_config  # noqa: F401
from pytorchdistributed_tpu.models.resnet import (  # noqa: F401
    ResNet,
    ResNetConfig,
    resnet18,
    resnet50,
)
