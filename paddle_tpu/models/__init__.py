"""Model zoo (reference: python/paddle/vision/models + PaddleNLP zoo shapes
named in BASELINE.md)."""
from .gpt import (  # noqa: F401
    GPTConfig, GPTModel, GPTForCausalLM, gpt, CONFIGS as GPT_CONFIGS,
    CacheQuantError,
)
from .resnet import (  # noqa: F401
    ResNet, BasicBlock, BottleneckBlock,
    resnet18, resnet34, resnet50, resnet101, resnet152,
)
from .bert import (  # noqa: F401
    BertConfig, BertModel, BertForSequenceClassification, BertForMaskedLM,
    ErnieModel, ErnieForSequenceClassification, ErnieForMaskedLM,
    bert, bert_for_sequence_classification, bert_for_masked_lm,
)
from .generation import generate, GenerationConfig  # noqa: F401
from .conformer import (  # noqa: F401
    ConformerCTC, conformer_tiny, conformer_s,
)
