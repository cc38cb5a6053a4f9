"""paddle_tpu.models — the model zoo."""
from .llama import (  # noqa: F401
    LlamaConfig, LlamaForCausalLM, LlamaModel, llama_tiny, llama_small,
    llama_mid, llama_3_8b,
)
from .gpt import (  # noqa: F401
    GPTConfig, GPTForCausalLM, GPTModel, gpt_tiny, gpt_345m,
)
from .moe_lm import (  # noqa: F401
    MoEConfig, MoEForCausalLM, MoEModel, moe_tiny,
)
from .keye_vl2 import (  # noqa: F401
    KeyeVL2Config, KeyeVL2ForCausalLM, KeyeVL2Model, keye_vl2_tiny,
)
from .lfm2_moe import (  # noqa: F401
    Lfm2MoeConfig, Lfm2MoeForCausalLM, Lfm2MoeModel, lfm2_moe_tiny,
)
from .smallthinker import (  # noqa: F401
    SmallThinkerConfig, SmallThinkerForCausalLM, SmallThinkerModel,
    smallthinker_tiny,
)
from .laguna import (  # noqa: F401
    LagunaConfig, LagunaForCausalLM, LagunaModel, laguna_tiny,
)
from .dit import (  # noqa: F401
    DiT, DiTConfig, dit_tiny, dit_s_2, dit_xl_2,
)
from .bert import (  # noqa: F401
    BertConfig, BertForMaskedLM, BertForSequenceClassification, BertModel,
    bert_tiny, bert_base, bert_large,
)
