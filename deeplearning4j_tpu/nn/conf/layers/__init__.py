"""Layer configuration zoo (reference nn/conf/layers/*; SURVEY.md §2.1)."""

from .base import LayerConf, FeedForwardLayerConf, BaseRecurrentLayerConf
from .feedforward import (DenseLayer, OutputLayer, RnnOutputLayer, LossLayer,
                          ActivationLayer, DropoutLayer, EmbeddingLayer,
                          AutoEncoder, RBM, CenterLossOutputLayer)
from .convolution import (ConvolutionLayer, Convolution1DLayer,
                          SubsamplingLayer, Subsampling1DLayer,
                          BatchNormalization, LocalResponseNormalization,
                          ZeroPaddingLayer, GlobalPoolingLayer)
from .recurrent import GravesLSTM, LSTM, GravesBidirectionalLSTM
from .attention import (SelfAttentionLayer, LayerNormalization,
                        RMSNormalization, TransformerFeedForward,
                        GatedFeedForward, TokenAndPositionEmbedding,
                        TokenEmbedding, Window)
from .latent_attention import LatentAttentionLayer
from .experts import RoutedExpertsLayer
from .state_space import Mamba2Layer
from .variational import VariationalAutoencoder

__all__ = [
    "LayerConf", "FeedForwardLayerConf", "BaseRecurrentLayerConf",
    "DenseLayer", "OutputLayer", "RnnOutputLayer", "LossLayer",
    "ActivationLayer", "DropoutLayer", "EmbeddingLayer", "AutoEncoder", "RBM",
    "CenterLossOutputLayer", "ConvolutionLayer", "Convolution1DLayer",
    "SubsamplingLayer", "Subsampling1DLayer", "BatchNormalization",
    "LocalResponseNormalization", "ZeroPaddingLayer", "GlobalPoolingLayer",
    "GravesLSTM", "LSTM", "GravesBidirectionalLSTM", "VariationalAutoencoder",
    "SelfAttentionLayer", "LayerNormalization",
    "TransformerFeedForward", "TokenAndPositionEmbedding",
    "RMSNormalization", "GatedFeedForward", "TokenEmbedding",
    "LatentAttentionLayer", "RoutedExpertsLayer", "Window", "Mamba2Layer",
]
