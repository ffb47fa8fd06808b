"""Kernels: the share of the layer's page region that the block tables
named, summed over the window's decode steps and latent-attention layers
(``serving.kv.pages_walked`` over ``serving.kv.pages_region``), in percent
(longdoc-saturated: 24 slots of 4k-32k tokens in a pool sized for 24 x
33.5k). Set by the traffic: how much of the region a step HAD to read."""
from benchmark.readers_kv import walked_share as read  # noqa: F401
