"""Kernels: the share of the full layers' page region that the block tables
named, summed over the window's decode steps and full layers
(``serving.kv.pages_walked`` over ``serving.kv.pages_region``), in percent
(mixedlen-saturated: 32 slots of 1k-34k tokens in a pool sized for 32 x
34.8k). Set by the traffic: how much of the region a step HAD to read."""
from benchmark.readers_kv import walked_share as read  # noqa: F401
