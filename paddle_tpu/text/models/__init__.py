from .bert import Bert, BertConfig  # noqa: F401
from .gpt import GPT, GPTConfig  # noqa: F401
from .kimi_k2 import KimiK2, KimiK2Config  # noqa: F401
from .longcat_flash import LongCatFlash, LongCatFlashConfig  # noqa: F401
from .olmo_hybrid import OlmoHybrid, OlmoHybridConfig  # noqa: F401
from .laguna import Laguna, LagunaConfig  # noqa: F401
from .mimo_v2 import MiMoV2Flash, MiMoV2Config  # noqa: F401
