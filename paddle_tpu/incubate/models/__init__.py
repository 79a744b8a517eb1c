from . import gpt  # noqa: F401
from .gpt import (  # noqa: F401
    GPTConfig, GPTModel, GPTForCausalLM, GPTPretrainingCriterion,
    gpt2_124m, gpt2_355m, gpt3_1p3b, gpt3_6p7b, shard_gpt,
    GPTEmbeddingPipe, GPTHeadPipe, gpt_pipeline_layers, GPTDecodeStep,
)
from . import longcat_flash  # noqa: F401
from .longcat_flash import (  # noqa: F401
    LongCatFlashConfig, LongCatFlashForCausalLM,
)
from . import joyai_llm_flash  # noqa: F401
from .joyai_llm_flash import (  # noqa: F401
    JoyAIFlashConfig, JoyAIFlashForCausalLM,
)
from . import lfm2_moe  # noqa: F401
from .lfm2_moe import (  # noqa: F401
    Lfm2MoeConfig, Lfm2MoeForCausalLM,
)
from . import mimo_v2_flash  # noqa: F401
from .mimo_v2_flash import (  # noqa: F401
    MiMoV2FlashConfig, MiMoV2FlashForCausalLM,
)
from . import solar_open2  # noqa: F401
from .solar_open2 import (  # noqa: F401
    SolarOpen2Config, SolarOpen2ForCausalLM,
)
