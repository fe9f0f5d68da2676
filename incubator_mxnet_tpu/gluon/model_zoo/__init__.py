"""Model zoo (reference ``python/mxnet/gluon/model_zoo/``)."""

from . import decoder
from . import gpt
from . import vision
from .decoder import HybridDecoder, get_decoder
from .gpt import GPTDecoder, get_gpt
from .vision import get_model
