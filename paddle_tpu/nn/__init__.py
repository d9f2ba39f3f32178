"""paddle_tpu.nn — neural network layers.

Reference analog: python/paddle/nn/ (modern API) + fluid/dygraph/layers.py.
"""
from . import functional  # noqa: F401
from . import initializer  # noqa: F401
from .activation_layers import *  # noqa: F401,F403
from .common_layers import *  # noqa: F401,F403
from .container import LayerDict, LayerList, ParameterList, Sequential  # noqa: F401
from .conv_layers import *  # noqa: F401,F403
from .layer import Layer  # noqa: F401
from .loss_layers import *  # noqa: F401,F403
from .norm_layers import *  # noqa: F401,F403
from .hybrid_layers import (  # noqa: F401
    GatedRMSNorm, GatedShortConv, GroupedQueryAttention, KimiDeltaAttention,
    LatentAttention, RMSNorm, ShortConv1D, SparseExpertShare, SwiGLU)
from .pool_layers import *  # noqa: F401,F403

# sequence / attention stacks
from .decode import (  # noqa: F401
    BeamSearchDecoder,
    beam_search_decode,
    beam_search_step,
    dynamic_decode,
    gather_tree,
    greedy_search_decode,
)
from .rnn import (  # noqa: F401
    GRU,
    GRUCell,
    LSTM,
    LSTMCell,
    RNN,
    BiRNN,
    SimpleRNN,
    SimpleRNNCell,
)
from .transformer import (  # noqa: F401
    MultiHeadAttention,
    Transformer,
    TransformerDecoder,
    TransformerDecoderLayer,
    TransformerEncoder,
    TransformerEncoderLayer,
)
from .clip_grad import ClipGradByGlobalNorm, ClipGradByNorm, ClipGradByValue  # noqa: F401

# remaining reference nn/__init__.py surface (round 5)
from . import functional as common  # noqa: F401  (reference re-exports the
#   functional submodules under these names)
from .functional import conv, extension, loss, norm  # noqa: F401
from .functional import common as _fcommon  # noqa: F401
vision = extension  # image_resize/space_to_depth/... live there
weight_norm_hook = norm
from .rnn import RNNCellBase  # noqa: F401
from .decode import BeamSearchDecoder as Decoder  # noqa: F401 — abstract
#   Decoder's only concrete reference subclass
from ..jit.control_flow import cond, while_loop  # noqa: F401
from ..static import InputSpec as Input  # noqa: F401
from .layers_extra import (  # noqa: F401
    DynamicRNN, HSigmoidLoss, NCELoss, PairwiseDistance, StaticRNN,
    TreeConv, ctc_greedy_decoder)
from .functional.extension import crf_decoding  # noqa: F401
