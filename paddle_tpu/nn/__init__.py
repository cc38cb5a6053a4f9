"""paddle_tpu.nn — parity with paddle.nn
(/root/reference/python/paddle/nn/__init__.py)."""
from . import functional  # noqa: F401
from . import quant  # noqa: F401
from . import initializer  # noqa: F401
from .layer.layers import Layer  # noqa: F401
from .layer.common import *  # noqa: F401,F403
from .layer.container import *  # noqa: F401,F403
from .layer.activation import *  # noqa: F401,F403
from .layer.conv import *  # noqa: F401,F403
from .layer.norm import *  # noqa: F401,F403
from .layer.pooling import *  # noqa: F401,F403
from .layer.loss import *  # noqa: F401,F403
from .layer.transformer import *  # noqa: F401,F403
from .layer.moe import (MoELayer, MoEShareLayer, SwitchGate,  # noqa: F401
                        GShardGate)
from .layer.rnn import *  # noqa: F401,F403
from .layer.extras import *  # noqa: F401,F403
from .decode import BeamSearchDecoder, dynamic_decode  # noqa: F401
from . import utils  # noqa: F401
from ..optimizer.clip import (  # noqa: F401 — paddle.nn.ClipGradBy* parity
    ClipGradByValue, ClipGradByNorm, ClipGradByGlobalNorm,
)


class ParamAttr:
    """paddle.ParamAttr-lite: carries name/initializer/trainable/
    learning_rate metadata into create_parameter."""

    def __init__(self, name=None, initializer=None, learning_rate=1.0,
                 regularizer=None, trainable=True, do_model_average=True,
                 need_clip=True):
        self.name = name
        self.initializer = initializer
        self.learning_rate = learning_rate
        self.regularizer = regularizer
        self.trainable = trainable
        self.need_clip = need_clip
