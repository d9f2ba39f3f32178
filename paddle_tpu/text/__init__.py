"""paddle_tpu.text (reference: python/paddle/text/ — dataset loaders).

Zero-egress: datasets read local cache files or generate synthetic stand-ins.
"""
from .datasets import (Conll05st, Imdb, Imikolov, Movielens,  # noqa: F401
                       UCIHousing, WMT14, WMT16)
from .generation import (generate, make_gpt_decode_step,  # noqa: F401
                         make_gpt_paged_decode_step, prefill)
from .models import (  # noqa: F401
    BertForQuestionAnswering,
    BertForSequenceClassification,
    BertModel,
    GPTModel,
    KimiLinearModel,
    Lfm2MoeModel,
)
