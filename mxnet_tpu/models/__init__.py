"""Model zoo: symbol builder functions.

TPU-native counterpart of the reference's symbol zoo
(``example/image-classification/symbols/`` — alexnet, vgg, googlenet,
inception-bn, resnet — plus the mnist nets built inline in
``example/image-classification/train_mnist.py:15-42``).  Each ``get_symbol``
returns a Symbol ending in a loss head, suitable for Module/FeedForward or
the ShardedTrainer.

All symbols are built NCHW, matching the reference layout; XLA re-lays-out
for the MXU internally, so the user-facing layout stays reference-compatible.
"""
from . import mlp
from . import lenet
from . import alexnet
from . import vgg
from . import googlenet
from . import inception_bn
from . import inception_v3
from . import resnet
from . import lstm
from . import gru
from . import rnn

from . import transformer
from . import transformer_moe
from . import transformer_mla_moe
from . import transformer_cca_moe
from . import transformer_hybrid_moe
from .mlp import get_symbol as get_mlp
from .lenet import get_symbol as get_lenet
from .alexnet import get_symbol as get_alexnet
from .vgg import get_symbol as get_vgg
from .googlenet import get_symbol as get_googlenet
from .inception_bn import get_symbol as get_inception_bn
from .inception_v3 import get_symbol as get_inception_v3
from .resnet import get_symbol as get_resnet

__all__ = ["transformer", "transformer_moe", "transformer_mla_moe",
           "transformer_cca_moe", "transformer_hybrid_moe", "mlp", "lenet", "alexnet",
           "vgg", "googlenet",
           "inception_bn", "inception_v3", "resnet", "lstm", "gru", "rnn",
           "get_mlp", "get_lenet", "get_alexnet", "get_vgg",
           "get_googlenet", "get_inception_bn", "get_inception_v3",
           "get_resnet"]
