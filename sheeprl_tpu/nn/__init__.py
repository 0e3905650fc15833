from .core import Activation, Module, activation, field, static
from .layers import Conv2d, ConvTranspose2d, LayerNorm, Linear, dropout
from .blocks import CNN, DeCNN, MLP, MultiDecoder, MultiEncoder, NatureCNN
from .attention import Attention, RMSNorm, block_causal_mask, rope
from .moe import RoutedExperts
from .recurrent import GRUCell, LayerNormGRUCell, LSTMCell, scan_cell
from .inits import init_kaiming_normal, init_orthogonal, map_layers

__all__ = [
    "Activation",
    "Module",
    "activation",
    "field",
    "static",
    "Linear",
    "Conv2d",
    "ConvTranspose2d",
    "LayerNorm",
    "dropout",
    "MLP",
    "CNN",
    "DeCNN",
    "NatureCNN",
    "MultiEncoder",
    "MultiDecoder",
    "RMSNorm",
    "Attention",
    "rope",
    "block_causal_mask",
    "RoutedExperts",
    "GRUCell",
    "LayerNormGRUCell",
    "LSTMCell",
    "scan_cell",
    "init_orthogonal",
    "init_kaiming_normal",
    "map_layers",
]
